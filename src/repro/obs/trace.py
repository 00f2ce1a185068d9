"""Phase spans and the opt-in jax profiler trace.

The driver loop (:func:`repro.core.driver.run_loop`) has four host-visible
phases per step — ``data`` (batch/key production), ``step`` (the jitted
dispatch), ``telemetry`` (the record hook) and ``checkpoint``.  A
:class:`Tracer` wraps each in a wall-clock span plus a
``jax.profiler.TraceAnnotation`` so the same labels show up in a profiler
timeline.  The grad/mix *sub*-phases live inside one fused jit and cannot
be wall-clocked from the host; the engine tags them with
``jax.named_scope("obs_grad"/"obs_mix")`` instead, which the profiler
trace (:class:`Profiler`, ``--profile-dir``) decomposes.

:func:`overlap_report` reads those same tags out of a step's jaxpr to
*prove* (or refute) overlap-eligibility: under stale-window gossip
(``AlgorithmSpec.delay > 0``) no ``obs_mix`` operation may transitively
consume an ``obs_grad`` output, so XLA's latency-hiding scheduler is free
to run the gossip collectives concurrently with the grad computation.
"""

from __future__ import annotations

import time

import jax
from jax.extend import core as jex_core

PHASES = ("data", "step", "telemetry", "checkpoint")


# ---------------------------------------------------------------------------
# Overlap verification: data-dependence between the obs_grad / obs_mix tags
# ---------------------------------------------------------------------------

def _eqn_scopes(eqn) -> str:
    """The named_scope stack an equation was traced under, as a string
    (e.g. ``'obs_mix/transpose[...]'``)."""
    try:
        return str(eqn.source_info.name_stack)
    except AttributeError:  # very old jax: no name stacks — report nothing
        return ""


def mix_depends_on_grad(jaxpr) -> bool:
    """Whether any ``obs_mix``-tagged equation of ``jaxpr`` transitively
    consumes a value produced under ``obs_grad``.

    Taint propagation over the (topologically ordered) equation list,
    treating each equation atomically: an equation whose inputs carry
    taint taints all its outputs.  Sub-jaxprs (scan/cond bodies) inherit
    the outer equation's name stack, so outer-equation granularity is a
    sound over-approximation.  False means the mix is data-independent of
    the step's gradient — the XLA scheduler MAY overlap them (the
    ``delay > 0`` contract); True means the mix serializes after the grad
    (every synchronous rule, where the mix payload contains the fresh
    update).
    """
    closed = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    tainted: set = set()
    for eqn in closed.eqns:
        scopes = _eqn_scopes(eqn)
        consumes = any(not isinstance(v, jex_core.Literal) and v in tainted
                       for v in eqn.invars)
        if "obs_mix" in scopes and consumes:
            return True
        if "obs_grad" in scopes or consumes:
            tainted.update(eqn.outvars)
    return False


def overlap_report(fn, *args, **kwargs) -> dict:
    """Trace ``fn(*args, **kwargs)`` (abstractly — nothing executes) and
    report whether its gossip mix is overlap-eligible:

    * ``overlapped``  — True when no ``obs_mix`` op transitively depends
      on an ``obs_grad`` output (the stale-window double-buffer contract);
    * ``mix_eqns`` / ``grad_eqns`` — tagged top-level equation counts
      (0 for both means the function was not engine-annotated and the
      verdict is vacuous).
    """
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    closed = getattr(jaxpr, "jaxpr", jaxpr)
    mix_eqns = sum(1 for e in closed.eqns if "obs_mix" in _eqn_scopes(e))
    grad_eqns = sum(1 for e in closed.eqns if "obs_grad" in _eqn_scopes(e))
    return {"overlapped": not mix_depends_on_grad(jaxpr),
            "mix_eqns": mix_eqns, "grad_eqns": grad_eqns}


class Tracer:
    """Wall-clock phase spans for the driver loop.

    ``span(phase)`` is a context manager; completed spans accumulate into
    ``totals``/``counts`` and queue in ``_pending`` until the next
    :meth:`drain` (the ObsRecorder attaches them to that step's event).

    ``annotate=True`` additionally wraps each span in a
    ``jax.profiler.TraceAnnotation`` so the labels land in a profiler
    timeline; it is off by default because the annotation costs a few
    microseconds per span on the hot path and is only readable when a
    trace (``--profile-dir``) is actually being captured.
    """

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self._pending: dict[str, float] = {}
        self._spans: dict[str, _Span] = {}

    def span(self, phase: str) -> "_Span":
        # One reusable context-manager object per phase: span() runs every
        # loop phase of every step, so it avoids allocating a generator
        # frame per call.  Phases never nest, so reuse is safe.
        s = self._spans.get(phase)
        if s is None:
            s = self._spans[phase] = _Span(self, phase)
        return s

    def drain(self) -> dict[str, float]:
        """Spans accumulated since the last drain (one step's worth)."""
        out, self._pending = self._pending, {}
        return out

    def summary(self) -> dict:
        """Per-phase totals for the run-summary event / report table."""
        return {
            phase: {"total_sec": self.totals[phase],
                    "count": self.counts.get(phase, 0),
                    "mean_ms": 1e3 * self.totals[phase]
                    / max(1, self.counts.get(phase, 0))}
            for phase in sorted(self.totals)
        }


class _Span:
    """Reusable timing context for one Tracer phase (see Tracer.span)."""

    __slots__ = ("tracer", "phase", "ann", "t0")

    def __init__(self, tracer: Tracer, phase: str):
        self.tracer = tracer
        self.phase = phase
        self.ann = None
        self.t0 = 0.0

    def __enter__(self):
        if self.tracer.annotate:
            self.ann = jax.profiler.TraceAnnotation(f"obs:{self.phase}")
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        dt = time.perf_counter() - self.t0
        tr, ph = self.tracer, self.phase
        tr.totals[ph] = tr.totals.get(ph, 0.0) + dt
        tr.counts[ph] = tr.counts.get(ph, 0) + 1
        tr._pending[ph] = tr._pending.get(ph, 0.0) + dt
        if self.ann is not None:
            ann, self.ann = self.ann, None
            ann.__exit__(et, ev, tb)
        return False


class Profiler:
    """Opt-in jax profiler trace of the first ``steps`` recorded steps.

    ``start()`` before the loop, ``maybe_stop(k)`` from the record hook
    (stops once ``steps`` steps have been observed), ``close()`` as a
    stop-on-exit guard.  Dumps a TensorBoard-loadable trace into ``dir``.
    """

    def __init__(self, directory: str, steps: int = 8):
        self.dir = directory
        self.steps = int(steps)
        self._active = False
        self._seen = 0

    def start(self):
        if not self._active:
            jax.profiler.start_trace(self.dir)
            self._active = True
        return self

    def maybe_stop(self, k: int) -> bool:
        """Count one recorded step; stop the trace after ``steps``."""
        del k
        if not self._active:
            return False
        self._seen += 1
        if self._seen >= self.steps:
            self.close()
            return True
        return False

    def close(self):
        if self._active:
            self._active = False
            jax.profiler.stop_trace()
