"""Unified training driver: the ONE place that stages gossip on device,
gathers per-step windows in-jit, warm-starts (or restores), records
eval/history, and runs the checkpoint cadence.

Consumed by :func:`repro.core.algorithms.run` (host reference),
:mod:`repro.launch.train` (distributed CLI), ``benchmarks/run.py`` and the
examples — none of them hand-roll a staging/driver loop anymore.

The staging contract (shared by every path): the whole schedule window —
one period of dense matrices, or the gossip plan's tensors — crosses the
host boundary ONCE, and the jitted step gathers its ``weights_per_step``
rounds by ``t % period`` index.  No per-step ``stacked()`` or host
transfer.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..obs import trace as obs_trace

PyTree = Any


# ---------------------------------------------------------------------------
# Gossip staging + in-jit window gather
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StagedGossip:
    """Device-resident gossip for a whole run.

    ``impl='dense'``: ``arrays`` is the (period, n, n) stacked window;
    the bound step gathers ``wps`` rounds by index.  ``impl='auto'``:
    ``arrays`` is the staged :class:`repro.core.gossip.GossipPlan` tensors;
    the step receives them plus the start round ``t``.
    """

    impl: str
    arrays: Any
    period: int
    wps: int
    static_t: bool = False


def stage_plan(plan) -> dict:
    """Upload a :class:`repro.core.gossip.GossipPlan`'s tensors to device
    ONCE — the canonical staging entry (``dist.collectives.stage_plan``
    delegates here).  The returned dict is passed unchanged to every jitted
    step, which indexes it by ``t % period``."""
    return jax.tree.map(jnp.asarray, plan.tensors())


def stage(schedule, *, wps: int, impl: str = "dense", total: int | None = None,
          plan=None, static_t: bool = False) -> StagedGossip:
    """Stage ``schedule`` on device once.

    ``total`` caps the dense window (host runs stage ``min(period, total)``
    rounds; pass None to always stage one full period — the CLI does, so a
    ``--restore`` continuation lands on the right phase).  For ``auto``,
    ``plan`` is the GossipPlan (defaults to one planned period).
    """
    if impl == "auto":
        if plan is None:
            plan = schedule.plan(0, schedule.period)
        return StagedGossip("auto", stage_plan(plan), plan.period, wps,
                            static_t=static_t)
    period = getattr(schedule, "period", None) or (total or 1)
    if total is not None:
        period = min(period, total)
    arrays = jnp.asarray(schedule.stacked(0, period))
    return StagedGossip("dense", arrays, period, wps)


def bind_step(staged: StagedGossip, core_step, *, donate: bool = False):
    """Jit ``core_step`` against the staged gossip.

    ``core_step(state, extra, gossip, t)`` — ``extra`` is the per-step
    input (a batch, a PRNG key, ...).  Dense: ``gossip`` arrives as the
    step's gathered ``(wps, n, n)`` window.  Auto: ``gossip`` is the plan
    tensors and ``t`` the start round (static when the plan dispatch is).

    ``donate=True`` donates the state argument, so the step updates it in
    place and input and output state are never both live — what lets a
    full-width model fit one device.  The caller must then own the state
    outright: the arrays passed in are deleted by the call, so no other
    reference to them (a user's ``x0``, a second leaf aliasing the same
    buffer) may be used again.

    Returns ``step(state, extra, t) -> (state, out)`` with the staged
    arrays closed over.
    """
    donate_argnums = (0,) if donate else ()
    if staged.impl == "auto":
        fn = jax.jit(core_step, donate_argnums=donate_argnums,
                     static_argnums=(3,) if staged.static_t else ())
        return lambda state, extra, t: fn(state, extra, staged.arrays, t)

    wps, period = staged.wps, staged.period

    def gathered(state, extra, Ws_all, t):
        idx = (t + jnp.arange(wps)) % period
        return core_step(state, extra, jnp.take(Ws_all, idx, axis=0), t)

    fn = jax.jit(gathered, donate_argnums=donate_argnums)
    return lambda state, extra, t: fn(state, extra, staged.arrays, t)


# ---------------------------------------------------------------------------
# Restore-or-warm + the loop
# ---------------------------------------------------------------------------

def restore_or_warm(state, *, restore: Optional[str] = None, load_fn=None,
                    warm: Optional[Callable] = None, spec=None):
    """Either restore ``(state, start_step)`` from a checkpoint or apply the
    rule's warm start — never both (a checkpoint already holds warm state).

    ``spec`` is the current run's :class:`repro.exp.ExperimentSpec` (when
    the caller has one): if the checkpoint was written with a
    reproducibility manifest (``<restore>.spec.json``), any mismatch on a
    scenario-defining field raises a warning before the restore proceeds.
    """
    if restore:
        if spec is not None:
            from ..exp import manifest as _mf  # deferred: exp imports core
            _mf.check_restore_spec(restore, spec)
        state, start_step = load_fn(restore, state)
        return state, int(start_step)
    return (warm(state) if warm is not None else state), 0


def run_loop(step, state, *, steps: int, wps: int, period: int,
             start_step: int = 0, extra_fn: Optional[Callable] = None,
             record: Optional[Callable] = None,
             checkpoint: Optional[str] = None, checkpoint_every: int = 50,
             save_fn=None, tracer=None):
    """The training loop every runtime shares.

    ``step(state, extra, t)`` — a :func:`bind_step` result; ``t`` advances
    by ``wps`` per step, taken modulo ``period``, and continues from
    ``start_step * wps`` so restored runs resume the schedule at the right
    phase.  ``extra_fn(k)`` supplies the per-step input.  ``record(k, t,
    state, out, dt)`` is called after every step; non-None returns are
    appended to the history.  ``save_fn(path, state, step)`` runs every
    ``checkpoint_every`` steps and once at the end.

    Every iteration runs under ``tracer.step(k)`` (a
    ``jax.profiler.StepTraceAnnotation``) and each phase in a span of
    :mod:`repro.obs.trace`: ``data`` = extra_fn, ``dispatch`` = the jitted
    step call, ``record`` = the record hook (``dt`` is the dispatch
    span's duration), ``checkpoint`` = save_fn.  ``tracer`` is the run's
    :class:`repro.obs.trace.Tracer`; without one the loop makes its own,
    so the spans are always recorded.
    """
    if tracer is None:
        tracer = obs_trace.Tracer()
    span = tracer.span
    history = []
    t = start_step * wps
    last = start_step + steps - 1
    for k in range(start_step, start_step + steps):
        with tracer.step(k):
            with span("data"):
                extra = extra_fn(k) if extra_fn is not None else None
            with span("dispatch") as d:
                state, out = step(state, extra, t % period)
            t += wps
            if record is not None:
                with span("record"):
                    rec = record(k, t, state, out, d.end - d.start)
                if rec is not None:
                    history.append(rec)
            if checkpoint and save_fn is not None and \
                    (k + 1) % checkpoint_every == 0 and k != last:
                with span("checkpoint"):
                    save_fn(checkpoint, state, k + 1)
    if checkpoint and save_fn is not None:
        with span("checkpoint"):
            save_fn(checkpoint, state, start_step + steps)
    return state, history


# ---------------------------------------------------------------------------
# Host-reference convenience (algorithms.run and the examples)
# ---------------------------------------------------------------------------

def run_algorithm(algo, x0: PyTree, grad_fn, weight_schedule, num_steps: int,
                  key: jax.Array, eval_fn=None, eval_every: int = 1,
                  gossip_impl: str = "dense", plan=None, telemetry=None,
                  obs: tuple = (), tracer=None):
    """Drive a host :class:`repro.core.algorithms.DecentralizedAlgorithm`
    over a :class:`repro.core.gossip.WeightSchedule`.

    ``gossip_impl='dense'`` stages one window of dense matrices;
    ``'auto'`` lowers the schedule through ``weight_schedule.plan`` and
    mixes via :func:`repro.core.algorithms.plan_step` — the same per-round
    structured dispatch the distributed runtime uses (``plan`` overrides
    the default one-period plan).  ``telemetry`` is an optional
    :class:`repro.sim.telemetry.TelemetryRecorder` or
    :class:`repro.obs.metrics.ObsRecorder` (anything with the
    ``record(k, t, state, out, dt)`` hook signature) invoked every step;
    when it also exposes ``eval_event(k, t, value)``, every recorded
    ``eval_fn`` point is forwarded to it (the optimality-gap feed).

    ``obs`` names in-jit metric scalars (:data:`repro.core.engine.
    OBS_METRICS`) to compute inside the step; they arrive at the record
    hook as ``out["obs"]`` device scalars.  ``tracer`` is the
    :class:`repro.obs.trace.Tracer` the loop's spans go to (see
    :func:`run_loop`).

    Returns (final_state, history) where history records ``eval_fn`` of the
    node-mean model x̄ every ``eval_every`` steps (plus the final step),
    keyed by the total gossip/oracle budget T consumed so far (the paper's
    Figure 2 x-axis).
    """
    state = algo.init(x0)
    key, k0 = jax.random.split(key)
    state = algo.warm(state, grad_fn, k0)
    wps = algo.weights_per_step
    total = max(1, num_steps * wps)
    obs = tuple(obs)
    if gossip_impl == "auto":
        from . import algorithms as alg  # deferred: algorithms imports driver
        if plan is None:
            plan = weight_schedule.plan(0, weight_schedule.period)
        pstep = alg.plan_step(algo, plan)
        staged = stage(weight_schedule, wps=wps, impl="auto", plan=plan,
                       static_t=(pstep.dispatch == "static"))

        def core(state, sub, tensors, t):
            out = pstep(state, grad_fn, tensors, t, sub, obs=obs)
            return (out[0], {"obs": out[1]}) if obs else (out, None)
    else:
        staged = stage(weight_schedule, wps=wps, total=total)

        def core(state, sub, weights, t):
            out = algo.step(state, grad_fn, weights, sub, obs=obs)
            return (out[0], {"obs": out[1]}) if obs else (out, None)

    step = bind_step(staged, core)

    def extra_fn(k):
        nonlocal key
        key, sub = jax.random.split(key)
        return sub

    def record(k, t, state, out, dt):
        if telemetry is not None:
            # inside run_loop's ``record`` span, on its tracer
            with obs_trace.span("record.telemetry"):
                telemetry.record(k, t, state, out, dt)
        if eval_fn is None:
            return None
        if k % eval_every == 0 or k == num_steps - 1:
            xbar = jax.tree.map(lambda x: jnp.mean(x, axis=0), state.x)
            val = jax.device_get(eval_fn(xbar))
            if telemetry is not None and hasattr(telemetry, "eval_event"):
                telemetry.eval_event(k, t, val)
            return (t, val)
        return None

    return run_loop(step, state, steps=num_steps, wps=wps,
                    period=staged.period, extra_fn=extra_fn, record=record,
                    tracer=tracer)
