"""Spans of the training loop, the compile counter, and the profiler trace.

The driver loop (:func:`repro.core.driver.run_loop`) runs every step
under ``jax.profiler.StepTraceAnnotation("train", step_num=k)`` and opens
one span per host phase; the ``arch`` record hook
(``repro.exp.build._run_arch``) opens the three ``record.*`` children:

* ``data`` -- ``extra_fn(k)``, the token stream;
* ``dispatch`` -- the jitted step call (on an async device only the
  enqueue);
* ``record`` -- the record hook, with the children
  ``record.telemetry`` (the chained ObsRecorder / TelemetryRecorder),
  ``record.sync`` (``jax.block_until_ready`` of the step's output; its
  end is the history row's ``ready`` stamp) and ``record.readback``
  (from the sync to the hook's return: the consensus sums' program, one
  transfer of them and the loss, the console line);
* ``checkpoint`` -- ``save_fn``.

A span (:class:`Span`) records its name, the step ``k``, its parent's
name, its start and end on ``time.perf_counter`` and the compiles that
happened inside it.  Every span goes into one process-wide ring buffer of
:data:`RING_SIZE` entries (:func:`spans`) and is also a
``jax.profiler.TraceAnnotation`` named ``repro/<name>``, so whenever a
profiler trace is active the spans share the device trace's timeline.
With no trace active a span costs about a microsecond, so they are always
on.

A process-wide compile counter (:func:`compile_counts`) listens to
``jax.monitoring``: backend compiles (a program compiled or loaded from
the persistent cache), the seconds of jaxpr tracing, MLIR lowering and
backend compiling, and the persistent cache's hits and misses.  Each
backend compile is credited to the innermost span open on the compiling
thread, and so to its step.

The grad/mix *sub*-phases live inside one fused jit and cannot be timed
from the host; the engine tags them with ``jax.named_scope("obs_grad" /
"obs_mix")`` instead, which a profiler trace (:class:`Profiler`,
``--profile-dir``) decomposes.  :func:`overlap_report` reads those same
tags out of a step's jaxpr to *prove* (or refute) overlap-eligibility:
under stale-window gossip (``AlgorithmSpec.delay > 0``) no ``obs_mix``
operation may transitively consume an ``obs_grad`` output, so XLA's
latency-hiding scheduler is free to run the gossip collectives
concurrently with the grad computation.
"""

from __future__ import annotations

import array
import collections
import statistics
import threading
import time
from typing import NamedTuple, Optional

import jax
from jax.extend import core as jex_core

# every span the training loop opens, parents before children
SPANS = ("data", "dispatch", "record", "record.telemetry", "record.sync",
         "record.readback", "checkpoint")
RING_SIZE = 65536


# ---------------------------------------------------------------------------
# The span ring and the compile counter (process-wide)
# ---------------------------------------------------------------------------

class Span(NamedTuple):
    """One closed span: ``start``/``end`` on ``time.perf_counter``;
    ``compiles`` counts the backend compiles credited to it (not to its
    children)."""

    name: str
    k: Optional[int]
    parent: Optional[str]
    start: float
    end: float
    compiles: int

    @property
    def dur(self) -> float:
        return self.end - self.start


_RING: collections.deque = collections.deque(maxlen=RING_SIZE)
_LOCAL = threading.local()
_COUNTS = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
           "cache_misses": 0}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_S_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                     "/jax/core/compile/jaxpr_to_mlir_module_duration",
                     _BACKEND_COMPILE)
_listening = False


def spans() -> list:
    """The ring buffer's spans, oldest first (at most :data:`RING_SIZE`)."""
    return list(_RING)


def span(name: str) -> "_Span":
    """A child of the innermost span open on this thread, recorded by that
    span's tracer: for code that runs inside the loop's spans (a record
    hook) but holds no :class:`Tracer`."""
    stack = _open()
    if not stack:
        raise RuntimeError(f"span {name!r} opened outside any span")
    return _Span(stack[-1].tracer, name)


def _open() -> list:
    """The spans open on this thread, innermost last."""
    try:
        return _LOCAL.stack
    except AttributeError:
        _LOCAL.stack = []
        return _LOCAL.stack


def _on_duration(event, sec, **kw):
    if event not in _COMPILE_S_EVENTS:
        return
    _COUNTS["compile_s"] += sec
    if event == _BACKEND_COMPILE:
        _COUNTS["compiles"] += 1
        stack = _open()
        if stack:
            stack[-1].compiles += 1


def _on_event(event, **kw):
    if event == "/jax/compilation_cache/cache_hits":
        _COUNTS["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _COUNTS["cache_misses"] += 1


def _listen() -> None:
    """Register the compile listeners, once per process."""
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)


def compile_counts() -> dict:
    """Process totals since the counter was first registered:
    ``compiles`` (backend compiles, each a program compiled or loaded from
    the persistent cache), ``compile_s`` (jaxpr trace + MLIR lowering +
    backend compile seconds), ``cache_hits`` and ``cache_misses`` of the
    persistent cache."""
    _listen()
    return dict(_COUNTS)


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
    """The loop's spans and their per-name statistics.

    ``step(k)`` is the context of one loop iteration (a
    ``StepTraceAnnotation`` and the ``k`` its spans carry); ``span(name)``
    is a context manager whose closed :class:`Span` lands in the ring and
    in this tracer's statistics; its parent is the innermost span open on
    this thread.  Durations also queue per name until the
    next :meth:`drain` (the ObsRecorder attaches them to the next step
    event), and so do compiles (:meth:`drain_compiles`).  ``profiler``, a
    :class:`Profiler` the run may attach, is told at the end of every step
    whether the step compiled.
    """

    def __init__(self):
        _listen()
        self.profiler: Optional[Profiler] = None
        self.k: Optional[int] = None
        self._stats: dict[str, _Stat] = {}
        self._pending: dict[str, float] = {}
        self._pending_compiles = 0
        self._step_compiles = 0

    def step(self, k: int) -> "_StepScope":
        return _StepScope(self, k)

    def span(self, name: str) -> "_Span":
        return _Span(self, name)

    def drain(self) -> dict[str, float]:
        """Seconds per span name closed since the last drain."""
        out, self._pending = self._pending, {}
        return out

    def drain_compiles(self) -> int:
        """Backend compiles in spans closed since the last call."""
        n, self._pending_compiles = self._pending_compiles, 0
        return n

    def summary(self) -> dict:
        """Per-span statistics over every call of the run, for the
        run-summary event / report table: ``parent``, ``count``,
        ``total_sec``, ``self_sec`` (less what its children cover),
        ``mean_ms``, ``median_ms``, ``max_ms`` and the step ``max_step``
        it came at, ``compiles`` and ``compiled_steps`` (the steps whose
        span compiled)."""
        out = {}
        for name in sorted(self._stats):
            st = self._stats[name]
            total = sum(st.durs)
            out[name] = {
                "parent": st.parent, "count": len(st.durs),
                "total_sec": total, "self_sec": st.self_total,
                "mean_ms": 1e3 * total / len(st.durs),
                "median_ms": 1e3 * statistics.median(st.durs),
                "max_ms": 1e3 * st.max, "max_step": st.max_k,
                "compiles": st.compiles,
                "compiled_steps": list(st.compiled_steps)}
        return out

    def _close(self, s: "_Span", dur: float) -> None:
        st = self._stats.get(s.name)
        if st is None:
            st = self._stats[s.name] = _Stat(s.parent_name)
        st.durs.append(dur)
        st.self_total += dur - s.child
        if dur > st.max:
            st.max, st.max_k = dur, s.k
        if s.compiles:
            st.compiles += s.compiles
            st.compiled_steps.append(s.k)
            self._step_compiles += s.compiles
            self._pending_compiles += s.compiles
        self._pending[s.name] = self._pending.get(s.name, 0.0) + dur


class _Stat:
    """One span name's calls: every duration (8 bytes each), self time,
    the longest call and its step, and the steps that compiled."""

    __slots__ = ("parent", "durs", "self_total", "max", "max_k", "compiles",
                 "compiled_steps")

    def __init__(self, parent: Optional[str]):
        self.parent = parent
        self.durs = array.array("d")
        self.self_total = self.max = 0.0
        self.max_k = None
        self.compiles = 0
        self.compiled_steps: list = []


class _Span:
    """Timing context of one Tracer span (see Tracer.span).  After exit,
    ``start``/``end`` hold the span's perf_counter bounds."""

    __slots__ = ("tracer", "name", "parent", "parent_name", "ann", "start",
                 "end", "child", "compiles", "k")

    def __init__(self, tracer: Tracer, name: str):
        self.tracer = tracer
        self.name = name

    def __enter__(self):
        stack = _open()
        self.parent = parent = stack[-1] if stack else None
        self.parent_name = parent.name if parent is not None else None
        stack.append(self)
        self.k = self.tracer.k
        self.child = 0.0
        self.compiles = 0
        self.ann = jax.profiler.TraceAnnotation("repro/" + self.name)
        self.ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        self.end = end = time.perf_counter()
        self.ann.__exit__(et, ev, tb)
        _open().pop()
        dur = end - self.start
        if self.parent is not None:
            self.parent.child += dur
        _RING.append(Span(self.name, self.k, self.parent_name, self.start,
                          end, self.compiles))
        self.tracer._close(self, dur)
        return False


class _StepScope:
    """One loop iteration: ``StepTraceAnnotation("train", step_num=k)``,
    the ``k`` its spans carry, and the profiler's step notification."""

    __slots__ = ("tracer", "k", "ann")

    def __init__(self, tracer: Tracer, k: int):
        self.tracer = tracer
        self.k = k

    def __enter__(self):
        tr = self.tracer
        tr.k = self.k
        tr._step_compiles = 0
        self.ann = jax.profiler.StepTraceAnnotation("train", step_num=self.k)
        self.ann.__enter__()
        return self

    def __exit__(self, et, ev, tb):
        self.ann.__exit__(et, ev, tb)
        tr = self.tracer
        tr.k = None
        if tr.profiler is not None and et is None:
            tr.profiler.step_done(self.k, tr._step_compiles)
        return False


class Profiler:
    """A jax profiler trace of ``steps`` steady steps, into ``directory``.

    Driven by the :class:`Tracer` it is attached to: the trace opens at
    the end of the first step whose spans saw no compile, so the warm-up
    and its compiles stay out of it, and closes ``steps`` steps later.
    ``close()`` is the stop-on-exit guard.  ``first`` is the first traced
    step, ``last`` the last (None until traced).
    """

    def __init__(self, directory: str, steps: int = 8):
        self.dir = directory
        self.steps = int(steps)
        self.first: Optional[int] = None
        self.last: Optional[int] = None
        self._active = False
        self._done = False

    def step_done(self, k: int, compiles: int) -> None:
        """Step ``k`` ended, having compiled ``compiles`` programs."""
        if self._active:
            self.last = k
            if k - self.first + 1 >= self.steps:
                self.close()
        elif not self._done and not compiles:
            jax.profiler.start_trace(self.dir)
            self._active = True
            self.first = k + 1

    def close(self):
        self._done = True
        if self._active:
            self._active = False
            jax.profiler.stop_trace()
