"""Lowering: realize an :class:`ExperimentSpec` into runnable pieces, and
``run(spec)`` — the one entry point every runtime shares.

``build(spec)`` resolves every string-keyed field through
:mod:`repro.exp.registry` and materializes the realized scenario — the
(post-fault) :class:`~repro.core.gossip.WeightSchedule`, the
:class:`~repro.core.engine.UpdateRule`, the gossip plan, the telemetry
recorder, and the model/data pieces of whichever runtime the spec's
``model.kind`` selects:

* ``arch``   — the distributed runtime: a registered architecture trained
  via :func:`repro.dist.steps.make_train_step` + the unified
  :mod:`repro.core.driver` staging/loop (what ``launch/train.py`` runs);
* ``logreg`` — the host reference runtime: the paper's §6 non-convex
  logistic regression driven by :func:`repro.core.driver.run_algorithm`
  (what the examples and paper-claims benchmarks run).

``run(spec)`` builds, writes the reproducibility manifest next to every
declared output, runs, and returns a :class:`Result`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import configs
from ..checkpoint import load_checkpoint, save_checkpoint
from ..core import algorithms as alg, driver, engine, gossip
from ..data import (logreg_dataset, logreg_dataset_dirichlet,
                    logreg_loss_and_grad, token_stream_for)
from ..obs import console as obs_console, metrics as obs_metrics, \
    optimality as obs_optimality, trace as obs_trace
from ..sim import faults as sim_faults, telemetry as sim_telemetry
from . import manifest as mf, registry
from .spec import ExperimentSpec


class Result(NamedTuple):
    """What ``run(spec)`` returns.  ``history`` is the runtime's record
    list (dicts with loss/consensus for ``arch``; ``(T, eval)`` pairs for
    ``logreg``); ``telemetry`` is the mixing-telemetry recorder when the
    scenario warranted one (faults, mobility, or ``run.telemetry`` set);
    ``built`` is the realized scenario (:class:`Built`) — consumers that
    need the realized schedule/plan read it here instead of re-building;
    ``serve`` is the :class:`repro.serve.ServeResult` of the post-training
    serve phase when ``spec.serve`` enables one, else None."""

    state: Any
    history: list
    telemetry: Optional[sim_telemetry.TelemetryRecorder]
    spec: ExperimentSpec
    built: "Built" = None
    serve: Any = None


@dataclasses.dataclass
class Built:
    """Everything ``build(spec)`` realized.  Scenario pieces (rule,
    schedule, plan, faults, telemetry) are populated for every model kind;
    ``cfg``/``model``/``stream`` only for ``arch``;
    ``grad_fn``/``eval_fn``/``x0`` only for ``logreg``."""

    spec: ExperimentSpec
    rule: engine.UpdateRule
    wps: int
    horizon: int
    schedule: Any                 # realized WeightSchedule (post-fault)
    plan: Any                     # GossipPlan | None (gossip_impl == auto)
    fault_models: list
    local_opt: Any
    telemetry: Optional[sim_telemetry.TelemetryRecorder]
    cfg: Any = None
    model: Any = None
    stream: Any = None
    grad_fn: Any = None
    eval_fn: Any = None
    x0: Any = None
    obs: Optional[obs_metrics.ObsRecorder] = None
    obs_names: tuple = ()
    tracer: Optional[obs_trace.Tracer] = None
    state_dim: Optional[int] = None   # per-node state entries (when known)

    @property
    def realized(self) -> dict:
        """The manifest's ``realized`` section: quantities a reader cannot
        derive from the spec alone."""
        out = {
            "period": int(self.schedule.period),
            "weights_per_step": int(self.wps),
            "horizon": int(self.horizon),
            "seed": int(self.spec.run.seed),
            "plan_kinds": (None if self.plan is None
                           else sorted(set(self.plan.kinds))),
        }
        c = self.spec.compression
        comp = {"scheme": c.scheme, "state_dim": self.state_dim}
        if c.enabled:
            comp.update(error_feedback=c.error_feedback, warmup=c.warmup,
                        group=c.group)
        if self.state_dim is not None:
            from ..core import compress
            comp["bytes_per_round"] = compress.payload_bytes(
                self.state_dim, c.scheme, c.group)
            comp["baseline_bytes_per_round"] = compress.payload_bytes(
                self.state_dim, "none")
        out["compression"] = comp
        if getattr(self.schedule, "is_sparse", False):
            e = self.schedule.edges_per_round
            snd = self.schedule.senders_per_round
            out["edges_per_round"] = {
                "min": int(e.min()), "max": int(e.max()),
                "mean": round(float(e.mean()), 1)}
            out["senders_per_round"] = {
                "min": int(snd.min()), "max": int(snd.max()),
                "mean": round(float(snd.mean()), 1)}
        if self.spec.obs.metrics:
            out["event_log"] = self.spec.obs.metrics
            out["obs_names"] = list(self.obs_names)
        sv = self.spec.serve
        if sv.enabled:
            out["serve"] = {"requests": sv.requests,
                            "fleet": sv.fleet or self.spec.run.nodes,
                            "batch": sv.batch, "routing": sv.routing}
        return out


def weights_per_step(algorithm) -> int:
    """Gossip rounds one step of this :class:`AlgorithmSpec` consumes (the
    paper's budget accounting) — derived from the engine rule, the single
    source of truth, so ``steps = T // weights_per_step(a)`` stays correct
    if a rule's round structure ever changes."""
    R = algorithm.R if algorithm.name == "mc_dsgt" else 1
    return engine.make_rule(algorithm.name, gamma=algorithm.gamma,
                            R=R).weights_per_step


def _validate(spec: ExperimentSpec) -> None:
    """Registry-driven validation: every string-keyed field must name a
    registered entry, and the error enumerates the legal values."""
    t, a, r, m = spec.topology, spec.algorithm, spec.run, spec.model
    if t.kind not in registry.TOPOLOGIES:
        raise ValueError(f"topology.kind={t.kind!r}: unknown "
                         f"(have {sorted(registry.TOPOLOGIES)})")
    if a.name not in registry.ALGORITHMS:
        raise ValueError(f"algorithm.name={a.name!r}: unknown "
                         f"(have {sorted(registry.ALGORITHMS)})")
    if a.local_opt not in registry.LOCAL_OPTS:
        raise ValueError(f"algorithm.local_opt={a.local_opt!r}: unknown "
                         f"(have {sorted(registry.LOCAL_OPTS)})")
    if r.gossip_impl not in registry.GOSSIP_IMPLS:
        raise ValueError(f"run.gossip_impl={r.gossip_impl!r}: unknown "
                         f"(have {sorted(registry.GOSSIP_IMPLS)})")
    if m.kind not in registry.MODEL_KINDS:
        raise ValueError(f"model.kind={m.kind!r}: unknown "
                         f"(have {sorted(registry.MODEL_KINDS)})")
    if a.delay < 0:
        raise ValueError(f"algorithm.delay={a.delay}: must be >= 0")
    if a.comm_interval < 1:
        raise ValueError(f"algorithm.comm_interval={a.comm_interval}: "
                         "must be >= 1")
    if t.pods < 1:
        raise ValueError(f"topology.pods={t.pods}: must be >= 1")
    if t.pods > 1 and r.nodes % t.pods:
        raise ValueError(f"topology.pods={t.pods} must divide "
                         f"run.nodes={r.nodes}")
    if t.kind in registry.SPARSE_TOPOLOGIES:
        if not 2 <= t.sample_k <= r.nodes:
            raise ValueError(f"topology.sample_k={t.sample_k}: the "
                             f"{t.kind!r} family samples a per-round "
                             f"cohort and needs 2 <= sample_k <= "
                             f"run.nodes={r.nodes}")
        if m.kind != "logreg":
            raise ValueError(f"topology.kind={t.kind!r} runs the host "
                             "reference runtime: model.kind must be "
                             "'logreg'")
        if a.name == "personalized":
            raise ValueError(
                f"algorithm.name='personalized' stages per-node dense "
                f"weight rows, which the edge-form {t.kind!r} family "
                "never materializes — use a dense topology")
        from ..sparse import DENSE_GUARD
        if r.nodes > DENSE_GUARD and r.gossip_impl != "auto":
            raise ValueError(
                f"run.nodes={r.nodes} exceeds the {DENSE_GUARD}-node dense "
                "guard: the dense host path would materialize (n, n) "
                "matrices — set run.gossip_impl='auto'")
    if m.kind == "logreg":
        if r.gossip_impl == "pallas":
            raise ValueError("model.kind='logreg' runs the host runtime: "
                             "gossip_impl must be 'dense' or 'auto'")
        if r.checkpoint or r.restore:
            raise ValueError("model.kind='logreg' does not support "
                             "checkpoint/restore (use the 'arch' runtime)")
    c = spec.compression
    if c.scheme not in registry.COMPRESSIONS:
        raise ValueError(f"compression.scheme={c.scheme!r}: unknown "
                         f"(have {sorted(registry.COMPRESSIONS)})")
    if c.group < 1:
        raise ValueError(f"compression.group={c.group}: must be >= 1")
    if c.warmup < 0:
        raise ValueError(f"compression.warmup={c.warmup}: must be >= 0")
    o = spec.obs
    if o.sink not in registry.SINKS:
        raise ValueError(f"obs.sink={o.sink!r}: unknown "
                         f"(have {sorted(registry.SINKS)})")
    if o.bound not in registry.OBS_BOUNDS:
        raise ValueError(f"obs.bound={o.bound!r}: unknown "
                         f"(have {sorted(registry.OBS_BOUNDS)})")
    if o.every < 1:
        raise ValueError(f"obs.every={o.every}: must be >= 1")
    registry.resolve_obs_names(o.names)  # raises on unknown metric names
    s = spec.serve
    if s.routing not in registry.ROUTING_POLICIES:
        raise ValueError(f"serve.routing={s.routing!r}: unknown "
                         f"(have {sorted(registry.ROUTING_POLICIES)})")
    if s.dtype not in registry.SERVE_DTYPES:
        raise ValueError(f"serve.dtype={s.dtype!r}: unknown "
                         f"(have {sorted(registry.SERVE_DTYPES)})")
    if s.requests < 0:
        raise ValueError(f"serve.requests={s.requests}: must be >= 0")
    if s.enabled:
        if m.kind != "arch":
            raise ValueError("serve.requests > 0 needs the 'arch' runtime: "
                             "serving decodes a trained transformer fleet "
                             f"(model.kind={m.kind!r})")
        if s.batch < 1 or s.max_new < 1 or s.prompt_len < 1:
            raise ValueError("serve.batch/max_new/prompt_len must be >= 1 "
                             f"(got {s.batch}/{s.max_new}/{s.prompt_len})")
        if not 0 <= s.fleet <= r.nodes:
            raise ValueError(f"serve.fleet={s.fleet}: must be 0 (= all "
                             f"run.nodes) or <= run.nodes={r.nodes}")


def build(spec: ExperimentSpec) -> Built:
    """Realize ``spec``: resolve registries, materialize the (possibly
    fault-degraded) weight schedule, lower the gossip plan, and construct
    the runtime-specific model/data pieces."""
    _validate(spec)
    rs, al = spec.run, spec.algorithm
    n = rs.nodes
    # R (consensus/accumulation rounds) is mc_dsgt's knob; every other rule
    # is defined at R=1 and the engine enforces it
    R = al.R if al.name == "mc_dsgt" else 1
    comp = registry.build_compression(spec.compression)
    rule = engine.make_rule(al.name, gamma=al.gamma, R=R, compression=comp,
                            delay=al.delay, comm_interval=al.comm_interval,
                            tau=al.tau)
    wps = rule.weights_per_step

    # horizon only matters for the non-periodic schedules (resampled
    # matching, mobility) and realized fault windows; the x4 cushion covers
    # --restore continuations (wrap past it is benign)
    horizon = (rs.steps + 1) * wps * 4
    sched = registry.build_topology(spec.topology, n, horizon=horizon,
                                    seed=rs.seed)
    fault_models = registry.build_channel_models(spec.channel, rs.seed)
    is_sparse = getattr(sched, "is_sparse", False)
    if fault_models:
        # ideal plan -> channel degradation -> repair -> (re-)lowering: the
        # realized window replaces the schedule wholesale, so both gossip
        # impls consume the same post-fault matrices.  Sparse schedules are
        # degraded edge-list-wise (per-edge hash streams, never densified).
        if is_sparse:
            from .. import sparse
            sched = sparse.realize_sparse_schedule(sched, fault_models)
        else:
            sched = sim_faults.realize_weight_schedule(sched, fault_models,
                                                       rounds=horizon)
    pods = spec.topology.pods if spec.topology.pods > 1 else None
    plan = (sched.plan(0, sched.period, pods=pods,
                       personalized=rule.personalized)
            if rs.gossip_impl == "auto" else None)
    telem = None
    if fault_models or rs.telemetry or comp is not None or rule.delay or \
            is_sparse or spec.topology.kind in registry.MOBILITY_TOPOLOGIES:
        if is_sparse:
            from ..sparse import SparseTelemetryRecorder as _Recorder
        else:
            _Recorder = sim_telemetry.TelemetryRecorder
        telem = _Recorder(sched, wps=wps, every=rs.log_every,
                          compression=comp, delay=rule.delay)
    built = Built(spec=spec, rule=rule, wps=wps, horizon=horizon,
                  schedule=sched, plan=plan, fault_models=fault_models,
                  local_opt=registry.build_local_opt(al.local_opt),
                  telemetry=telem, tracer=obs_trace.Tracer())
    if spec.obs.enabled:
        _build_obs(built)

    if spec.model.kind == "arch":
        from ..models import build as build_model
        cfg = configs.get(spec.model.arch)
        if spec.model.preset == "reduced":
            cfg = cfg.reduced()
        built.cfg = cfg
        built.model = build_model(cfg)
        built.stream = token_stream_for(
            cfg, n, R, spec.data.batch, spec.data.seq, seed=rs.seed,
            active_vocab=spec.data.active_vocab,
            hetero_alpha=spec.data.hetero_alpha)
        try:  # abstract eval only — no weight materialization
            shapes = jax.eval_shape(
                lambda key: built.model.init(key, jnp.float32),
                jax.random.key(0))
            built.state_dim = sum(int(l.size)
                                  for l in jax.tree.leaves(shapes))
        except Exception:
            built.state_dim = None
    else:
        mr = spec.model
        if spec.data.hetero_alpha is not None:
            H, y = logreg_dataset_dirichlet(n, mr.m, mr.d,
                                            alpha=spec.data.hetero_alpha,
                                            seed=rs.seed)
        else:
            H, y = logreg_dataset(n, mr.m, mr.d, seed=rs.seed)
        _, _, stoch, _, gnorm2 = logreg_loss_and_grad(rho=mr.rho)
        batch = spec.data.batch
        built.grad_fn = lambda xs, key: stoch(xs, H, y, key, batch)
        built.eval_fn = lambda xb: gnorm2(xb, H, y)
        built.x0 = jnp.zeros((n, mr.d))
        built.state_dim = mr.d
    return built


def _effective_beta(sched, period: int, cap: int = 64) -> float:
    """Measured per-round mixing parameter of the realized schedule: the
    window contraction over (up to ``cap`` rounds of) one period, taken to
    the per-round geometric mean — what the lower-bound floor's network
    term should be evaluated at."""
    rounds = max(1, min(int(period), cap))
    if getattr(sched, "is_sparse", False):
        # edge-list schedules never densify: the window contraction comes
        # from power iteration on the participant subspace
        from .. import sparse
        c = 1.0 - sparse.sparse_windowed_gap(
            [sched.round(t) for t in range(rounds)])
    else:
        c = gossip.consensus_contraction(sched, rounds)
    c = min(max(float(c), 0.0), 1.0 - 1e-9)
    return c ** (1.0 / rounds)


def _build_obs(built: Built) -> None:
    """Attach the repro.obs bundle to a Built: the event sink, the
    optimality-gap tracker for this spec's cell, the optional profiler
    (driven by the run's span tracer), and the
    :class:`~repro.obs.metrics.ObsRecorder` tying them together with the
    tracer (chaining the existing TelemetryRecorder when the scenario has
    one, instead of replacing it)."""
    spec = built.spec
    rs, al, o = spec.run, spec.algorithm, spec.obs
    built.obs_names = registry.resolve_obs_names(o.names, built.rule)
    cell = obs_optimality.cell_key(al.name, spec.topology.kind,
                                   registry.channel_label(spec.channel))
    gap = obs_optimality.GapTracker(
        cell=cell, n=rs.nodes,
        beta=_effective_beta(built.schedule, built.schedule.period),
        bound=o.bound)
    profiler = (obs_trace.Profiler(o.profile_dir, o.profile_steps)
                if o.profile_dir else None)
    built.tracer.profiler = profiler
    from .spec import spec_hash
    meta = {"name": f"{al.name} on {spec.topology.kind}",
            "spec_hash": spec_hash(spec), "cell": cell,
            "algo": al.name, "topology": spec.topology.kind,
            "channel": registry.channel_label(spec.channel),
            "model": spec.model.kind, "n": rs.nodes, "steps": rs.steps,
            "weights_per_step": built.wps,
            "gossip_impl": rs.gossip_impl, "every": o.every,
            "obs_names": list(built.obs_names)}
    # profile-only runs (profile_dir set, no metrics path) still need a
    # sink for the recorder's meta/summary events — an in-memory one
    sink = (obs_metrics.MemorySink() if o.sink == "jsonl" and not o.metrics
            else registry.build_sink(o))
    built.obs = obs_metrics.ObsRecorder(
        sink, every=o.every, telemetry=built.telemetry,
        tracer=built.tracer, gap=gap, profiler=profiler, meta=meta)


# ---------------------------------------------------------------------------
# run(spec): the one entry
# ---------------------------------------------------------------------------

def run(spec: ExperimentSpec, *, quiet: bool = False) -> Result:
    """Build and execute ``spec`` end to end on its runtime, writing the
    reproducibility manifest next to every declared output (checkpoint,
    telemetry, event log).  The telemetry/event-log manifests are written
    up front; the checkpoint manifest is written only AFTER the restore
    check, so resuming in place (checkpoint == restore) still compares
    against the ORIGINAL run's manifest before overwriting it."""
    built = build(spec)
    if spec.run.telemetry:
        mf.write_manifest(spec.run.telemetry, spec, realized=built.realized)
    if spec.obs.metrics:
        mf.write_manifest(spec.obs.metrics, spec, realized=built.realized)
    try:
        if spec.model.kind == "arch":
            res = _run_arch(built, quiet=quiet)
        else:
            res = _run_logreg(built)
        if spec.serve.enabled:
            # serve phase runs inside the try so its per-request obs
            # events land before the sink closes
            res = res._replace(serve=_run_serve(built, res.state,
                                                quiet=quiet))
        return res
    finally:
        if built.obs is not None:
            built.obs.close()


def _run_logreg(built: Built) -> Result:
    """Host reference runtime: the engine rule bound to the stacked-einsum
    (or planned) mixer, driven by :func:`repro.core.driver.run_algorithm`."""
    spec, rs = built.spec, built.spec.run
    algo = alg.from_rule(built.rule, built.local_opt)
    state, history = driver.run_algorithm(
        algo, built.x0, built.grad_fn, built.schedule, rs.steps,
        jax.random.key(rs.seed), eval_fn=built.eval_fn,
        eval_every=rs.eval_every, gossip_impl=rs.gossip_impl,
        plan=built.plan,
        telemetry=(built.obs if built.obs is not None else built.telemetry),
        obs=built.obs_names, tracer=built.tracer)
    if rs.telemetry and built.telemetry is not None:
        built.telemetry.dump(rs.telemetry)
    return Result(state=state, history=history, telemetry=built.telemetry,
                  spec=spec, built=built)


def _run_arch(built: Built, *, quiet: bool = False) -> Result:
    """Distributed runtime: the engine rule bound to the mesh/plan mixers
    via :func:`repro.dist.steps.make_train_step`, with the unified
    stage/bind/loop driver, checkpointing and loss/consensus logging."""
    from ..dist import steps as dsteps

    spec, rs = built.spec, built.spec.run
    stream, telem = built.stream, built.telemetry
    con = obs_console.Console(quiet=quiet)
    init_state, warm_start, train_step = dsteps.make_train_step(
        built.model, built.cfg, algo=spec.algorithm.name,
        gamma=spec.algorithm.gamma, R=built.rule.R,
        gossip_impl=rs.gossip_impl, plan=built.plan,
        local_opt=built.local_opt,
        compression=built.rule.compression,
        delay=built.rule.delay, comm_interval=built.rule.comm_interval,
        obs=built.obs_names)

    state = init_state(jax.random.key(rs.seed), rs.nodes, jnp.float32)
    state, start_step = driver.restore_or_warm(
        state, restore=rs.restore, load_fn=load_checkpoint,
        warm=lambda s: warm_start(s, stream.batch_at(0)), spec=spec)
    if rs.restore:
        con.print(f"restored step {start_step} from {rs.restore}")
    if rs.checkpoint:
        # written after the restore check (resume-in-place must be compared
        # against the original manifest first) but before the loop, so even
        # interrupted runs stay attributable
        mf.write_manifest(rs.checkpoint, built.spec, realized=built.realized)

    # Stage the whole period's gossip tensors on device ONCE; the jitted
    # step indexes them by (t mod period) — no per-step stacked()/transfer.
    staged = driver.stage(
        built.schedule, wps=built.wps,
        impl=("auto" if rs.gossip_impl == "auto" else "dense"),
        plan=built.plan,
        static_t=(rs.gossip_impl == "auto"
                  and train_step.gossip_dispatch == "static"))
    core = (train_step if rs.gossip_impl == "auto"
            else lambda state, batch, W, t: train_step(state, batch, W))
    # the state is this run's own (fresh init or restore), so the step may
    # donate it: a full-width model then fits one device
    step_fn = driver.bind_step(staged, core, donate=True)

    span = built.tracer.span
    recorder = built.obs if built.obs is not None else telem

    def record(k, t, state, out, dt):
        tl = None
        if recorder is not None:
            with span("record.telemetry"):
                tl = recorder.record(k, t, state, out, dt)
        if k % rs.log_every != 0:
            return None
        # on an async device ``dt`` is the dispatch only; ``ready`` is the
        # host clock once the step has finished (the sync span's end), so
        # differences of it between logged steps are true step times
        with span("record.sync") as sync:
            jax.block_until_ready((state, out))
        with span("record.readback"):
            if tl is not None:
                loss, ce = float(out["loss"]), tl["consensus"]
            else:
                # one device program and one transfer for both numbers
                loss, sums = jax.device_get(
                    (out["loss"], sim_telemetry.consensus_sums(state.x)))
                loss, ce = (float(loss),
                            sim_telemetry.consensus_from_sums(sums))
            extra = ""
            if tl is not None:
                ed = tl["eff_diameter"]
                gap = tl["spectral_gap"]
                extra = (f"  gap "
                         f"{gap if gap is not None else float('nan'):.3f}"
                         f"  eff_diam {ed if ed is not None else '-'}")
            con.print(f"step {k:5d}  T={t:6d}  loss {loss:.4f}  "
                      f"consensus {ce:.3e}{extra}  {dt:.2f}s")
            return {"step": k, "loss": loss, "consensus": ce,
                    "sec": round(dt, 3), "ready": sync.end}

    state, history = driver.run_loop(
        step_fn, state, steps=rs.steps, wps=built.wps, period=staged.period,
        start_step=start_step, extra_fn=lambda k: stream.batch_at(k + 1),
        record=record, checkpoint=rs.checkpoint, save_fn=save_checkpoint,
        tracer=built.tracer)
    if rs.checkpoint:
        con.event("saved", path=rs.checkpoint)
    if rs.telemetry and telem is not None:
        telem.dump(rs.telemetry)
        con.event("wrote_telemetry", path=rs.telemetry)
    return Result(state=state, history=history, telemetry=telem, spec=spec,
                  built=built)


def _run_serve(built: Built, state: Any, *, quiet: bool = False):
    """Post-training serve phase: slice the first ``serve.fleet`` node
    copies out of the trained stacked state and serve them with continuous
    batching (:func:`repro.serve.serve_fleet`), emitting per-request obs
    events through the run's recorder."""
    from ..serve import serve_fleet

    sv = built.spec.serve
    F = sv.fleet or built.spec.run.nodes
    fleet = jax.tree.map(lambda l: l[:F], state.x)
    res = serve_fleet(built.model, fleet, sv, obs=built.obs)
    con = obs_console.Console(quiet=quiet)
    tp = res.throughput
    con.print(f"served {tp['requests']} requests over fleet {res.fleet}  "
              f"decode {tp['decode_tok_s']:.0f} tok/s  "
              f"p50 {tp['latency_p50_ms']:.1f}ms  "
              f"p95 {tp['latency_p95_ms']:.1f}ms")
    return res
