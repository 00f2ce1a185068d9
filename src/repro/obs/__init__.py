"""repro.obs — unified observability for both runtimes.

The paper's contribution is a *complexity* statement (iterations x
communication to reach ε-stationarity); this package is the measurement
layer that lets the repo see its own complexity:

* :mod:`repro.obs.metrics` — the :class:`MetricsSink` protocol with a JSONL
  :class:`EventLog` backend, and the :class:`ObsRecorder` driver hook that
  batches the engine's in-jit step scalars (grad norm, consensus distance,
  mixing residual, tracker drift — computed once in
  :mod:`repro.core.engine` for BOTH runtimes) and flushes them host-side
  every ``every`` steps, so observation adds no device syncs to the hot
  path;
* :mod:`repro.obs.trace` — the training loop's always-on spans
  (data/dispatch/record/record.*/checkpoint) in a process-wide ring
  buffer, each also a ``jax.profiler.TraceAnnotation``; the process's
  compile counter, crediting each compile to its span and step; and the
  opt-in ``--profile-dir`` jax profiler trace of steady steps;
* :mod:`repro.obs.optimality` — online optimality-gap tracking of the
  measured ||∇f||² trajectory against the paper's lower bound
  (:mod:`repro.core.lower_bound`) per (algorithm x topology-class x
  channel) cell;
* :mod:`repro.obs.report` — ``python -m repro.obs.report <log.jsonl>``
  renders the run summary (phase table, metric sparklines, optimality
  gap);
* :mod:`repro.obs.console` — the one progress-output helper (honors
  ``--quiet``, keeps stdout machine-parseable).

Enable it declaratively: ``ExperimentSpec(obs=ObsSpec(metrics="run.jsonl"))``
or ``launch/train.py --metrics run.jsonl [--metrics-every N]
[--profile-dir DIR]``.
"""

from .console import Console  # noqa: F401
from .metrics import (  # noqa: F401
    EVENT_FIELDS,
    OBS_METRICS,
    ChainSink,
    EventLog,
    MemorySink,
    MetricsSink,
    ObsRecorder,
    read_events,
)
from .optimality import GapTracker, cell_key, theoretical_floor  # noqa: F401
from .trace import (  # noqa: F401
    SPANS,
    Profiler,
    Span,
    Tracer,
    compile_counts,
    mix_depends_on_grad,
    overlap_report,
    spans,
)
