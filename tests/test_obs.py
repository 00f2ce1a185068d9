"""repro.obs: in-jit metric parity across runtimes, flush completeness,
sinks, tracing, the optimality gap, report rendering, and the telemetry
round cache."""

import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg, driver, engine, gossip
from repro.obs import (
    Console,
    EventLog,
    GapTracker,
    MemorySink,
    ObsRecorder,
    Profiler,
    Tracer,
    cell_key,
    read_events,
    theoretical_floor,
)
from repro.obs import metrics as obs_metrics, optimality, report
from repro.obs import trace as obs_trace
from repro.sim.telemetry import TelemetryRecorder

N, D = 4, 6
KEY = jax.random.key(0)
TARGETS = jnp.asarray(np.random.default_rng(7).normal(size=(N, D)),
                      jnp.float32)


class _QuadModel:
    """Dist-runtime model with the same oracle as the host quadratic:
    loss 0.5 ||w - target||^2 per node, so grad = w - target."""

    def init(self, key, dtype):
        del key
        return {"w": jnp.zeros((D,), dtype)}

    def train_loss(self, params, batch):
        return 0.5 * jnp.sum((params["w"] - batch["t"][0]) ** 2)


def _host_grad(xs, key):
    del key
    return xs - TARGETS


def _dist_batch(R):
    # (n, R, b=1, d): every microbatch repeats the node's target, so the
    # R-sample mean equals the host's deterministic oracle
    t = jnp.broadcast_to(TARGETS[:, None, None, :], (N, R, 1, D))
    return {"t": t}


def _sched():
    return gossip.theorem3_weight_schedule(N, 0.75)


def _series(algo_name, R, impl, runtime, steps=3):
    """Per-step obs dicts for one (algorithm, gossip impl, runtime)."""
    from repro.dist import steps as dsteps

    sched = _sched()
    rule = engine.make_rule(algo_name, gamma=0.1, R=R)
    names = engine.default_obs(rule)
    wps = rule.weights_per_step
    plan = sched.plan(0, sched.period)
    tensors = driver.stage_plan(plan)
    out = []
    if runtime == "host":
        algo = alg.from_rule(rule)
        state = algo.init(jnp.zeros((N, D)))
        state = algo.warm(state, _host_grad, KEY)
        pstep = alg.plan_step(algo, plan)
        for k in range(steps):
            t = k * wps % sched.period
            if impl == "dense":
                Ws = jnp.asarray(sched.stacked(t, wps))
                state, scal = algo.step(state, _host_grad, Ws, KEY,
                                        obs=names)
            else:
                state, scal = pstep(state, _host_grad, tensors, t, KEY,
                                    obs=names)
            out.append(jax.device_get(scal))
    else:
        init_state, warm_start, train_step = dsteps.make_train_step(
            _QuadModel(), None, algo=algo_name, gamma=0.1, R=R,
            clip=None, gossip_impl=impl, plan=(plan if impl == "auto"
                                               else None), obs=names)
        batch = _dist_batch(R)
        state = init_state(KEY, N, jnp.float32)
        state = warm_start(state, batch)
        for k in range(steps):
            t = k * wps % sched.period
            if impl == "dense":
                Ws = jnp.asarray(sched.stacked(t, wps))
                state, o = train_step(state, batch, Ws)
            else:
                state, o = train_step(state, batch, tensors, t)
            out.append(jax.device_get(o["obs"]))
    return out


@pytest.mark.parametrize("impl", ["dense", "auto"])
@pytest.mark.parametrize("algo_name,R", [("dsgd", 1), ("mc_dsgt", 2)])
def test_metric_parity_host_vs_dist(algo_name, R, impl):
    """Both runtimes bind the SAME engine metrics: identical oracle +
    schedule must emit matching grad-norm/consensus/... series."""
    host = _series(algo_name, R, impl, "host")
    dist = _series(algo_name, R, impl, "dist")
    assert len(host) == len(dist) == 3
    for k, (h, d) in enumerate(zip(host, dist)):
        assert set(h) == set(d)
        for name in h:
            np.testing.assert_allclose(
                float(h[name]), float(d[name]), rtol=1e-5, atol=1e-6,
                err_msg=f"{algo_name}/{impl} step {k} metric {name}")
    # the series must be non-trivial: gradients exist, and without exact
    # averaging (dsgd's single round) nodes disagree
    assert float(host[0]["grad_norm"]) > 0.1
    if algo_name == "dsgd":
        assert float(host[-1]["consensus"]) > 0


@pytest.mark.parametrize("algo_name,has_tracker",
                         [("dsgd", False), ("local_sgd", False),
                          ("dsgt", True), ("mc_dsgt", True),
                          ("gt_local", True), ("d2", False)])
def test_default_obs_per_rule(algo_name, has_tracker):
    rule = engine.make_rule(algo_name, gamma=0.1,
                            R=(2 if algo_name == "mc_dsgt" else 1))
    names = engine.default_obs(rule)
    assert ("tracker_residual" in names) == has_tracker
    assert "grad_norm" in names and "consensus" in names


def test_tracking_invariant_small_residual():
    """mean(h) = mean(g) under doubly-stochastic mixing: with no clipping
    and f32 trackers the measured residual is numerical noise."""
    series = _series("mc_dsgt", 2, "dense", "dist", steps=4)
    for s in series:
        assert float(s["tracker_residual"]) < 1e-4


def test_every_flush_loses_no_events():
    """every > 1 batches host transfers but every recorded step must land
    in the sink (tail flushed by close)."""
    sink = MemorySink()
    rec = ObsRecorder(sink, every=4)
    for k in range(10):  # 10 % 4 != 0: the tail only flushes on close
        rec.record(k, (k + 1) * 2, None,
                   {"loss": jnp.float32(k), "obs": {"grad_norm":
                                                    jnp.float32(1.0 + k)}},
                   0.01)
    rec.close()
    steps = [e for e in sink.events if e["event"] == "step"]
    assert [e["step"] for e in steps] == list(range(10))
    assert [e["grad_norm"] for e in steps] == [1.0 + k for k in range(10)]
    assert sink.events[-1]["event"] == "summary"
    assert sink.closed


def test_event_log_jsonl(tmp_path):
    path = str(tmp_path / "sub" / "log.jsonl")  # parent dir auto-created
    log = EventLog(path)
    rec = ObsRecorder(log, every=2, meta={"name": "t", "n": N})
    rec.record(0, 2, None, {"obs": {"grad_norm": jnp.float32(3.0)}}, 0.5)
    rec.eval_event(0, 2, 0.25)
    rec.close()
    events = read_events(path)
    assert [e["event"] for e in events] == ["meta", "step", "eval",
                                            "summary"]
    assert events[0]["n"] == N
    assert events[1]["grad_norm"] == 3.0
    assert read_events(path, "eval") == [{"event": "eval", "step": 0,
                                          "t": 2, "value": 0.25}]


def test_telemetry_chained_not_replaced():
    """An existing TelemetryRecorder rides along: its windowed fields land
    on the step events AND its own history keeps filling."""
    sched = _sched()
    telem = TelemetryRecorder(sched, wps=2, window=4)
    sink = MemorySink()
    rec = ObsRecorder(sink, every=1, telemetry=telem)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(N, D)),
                    jnp.float32)

    class _S:
        pass

    s = _S()
    s.x = x
    for k in range(3):
        rec.record(k, (k + 1) * 2, s,
                   {"obs": {"consensus": jnp.float32(1.0)}}, 0.01)
    rec.close()
    steps = [e for e in sink.events if e["event"] == "step"]
    assert len(telem.history) == 3 == len(steps)
    assert all("spectral_gap" in e and "kinds" in e for e in steps)
    # the in-jit consensus wins over the recorder's host-side copy
    assert all(e["consensus"] == 1.0 for e in steps)


def test_telemetry_cache_matches_uncached():
    sched = _sched()
    cached = TelemetryRecorder(sched, wps=2, window=6, cache=True)
    plain = TelemetryRecorder(sched, wps=2, window=6, cache=False)

    class _S:
        x = jnp.ones((N, D))

    for k in range(8):
        a = cached.record(k, (k + 1) * 2, _S(), None, 0.0)
        b = plain.record(k, (k + 1) * 2, _S(), None, 0.0)
        assert a == b
    # eviction: only rounds inside the current window stay cached
    assert all(r >= 16 - 6 for r in cached._rounds)


def test_resolve_names():
    assert obs_metrics.resolve_names(None) == ()
    assert obs_metrics.resolve_names("") == ()
    assert obs_metrics.resolve_names("grad_norm, consensus") == \
        ("grad_norm", "consensus")
    assert obs_metrics.resolve_names("auto") == engine.OBS_METRICS
    rule = engine.make_rule("dsgd", gamma=0.1)
    assert "tracker_residual" not in obs_metrics.resolve_names("auto", rule)
    with pytest.raises(ValueError, match="unknown obs metric"):
        obs_metrics.resolve_names("grad_norm,bogus")


def test_tracer_spans_and_drain():
    tr = Tracer()
    with tr.span("dispatch"):
        pass
    with tr.span("dispatch"):
        pass
    with tr.span("data"):
        pass
    pending = tr.drain()
    assert set(pending) == {"dispatch", "data"}
    assert tr.drain() == {}  # drained
    s = tr.summary()
    assert s["dispatch"]["count"] == 2 and s["data"]["count"] == 1
    assert s["dispatch"]["total_sec"] >= 0
    assert s["dispatch"]["parent"] is None


def test_profiler_writes_trace(tmp_path):
    prof = Profiler(str(tmp_path / "trace"), steps=2)
    prof.step_done(0, compiles=3)  # a compiling step opens nothing
    assert prof.first is None
    prof.step_done(1, compiles=0)  # opens after the first clean step
    assert prof.first == 2
    prof.step_done(2, compiles=0)
    prof.step_done(3, compiles=0)  # stops after `steps` traced steps
    assert (prof.first, prof.last) == (2, 3)
    prof.step_done(4, compiles=0)  # never reopens
    prof.close()  # idempotent
    assert (prof.first, prof.last) == (2, 3)
    assert os.path.isdir(str(tmp_path / "trace"))


def test_theoretical_floor_regimes():
    # statistical term ~ 1/sqrt(nT): quadrupling T halves it
    f1 = theoretical_floor(1000, n=8, beta=0.0, sigma=1.0)
    f4 = theoretical_floor(4000, n=8, beta=0.0, sigma=1.0)
    net1 = 1.0 / 1000  # beta=0 network term = Delta L / T
    net4 = 1.0 / 4000
    assert (f1 - net1) / (f4 - net4) == pytest.approx(2.0, rel=1e-6)
    # network term scales as 1/(1-beta): beta .99 vs .5 is exactly 50x
    assert theoretical_floor(1000, n=8, beta=0.99, sigma=0.0) == \
        pytest.approx(50 * theoretical_floor(1000, n=8, beta=0.5,
                                             sigma=0.0))
    # full-batch: sigma=0 leaves only the network term
    assert theoretical_floor(100, n=4, beta=0.5, sigma=0.0) == \
        pytest.approx(1.0 / (0.5 * 100))


def test_gap_tracker_summary_and_rate():
    g = GapTracker(cell=cell_key("mc_dsgt", "sun", "ideal"), n=8, beta=0.5)
    for t in range(1, 200):
        g.update(t * 4, 10.0 / (t * 4))  # ~ T^{-1} decay
    s = g.summary()
    assert s["cell"] == "mc_dsgt/sun/ideal"
    assert s["T"] == 199 * 4
    assert s["best_grad_sq"] == pytest.approx(10.0 / (199 * 4))
    assert s["floor"] == pytest.approx(
        theoretical_floor(199 * 4, n=8, beta=0.5))
    assert s["gap_ratio"] == pytest.approx(s["best_grad_sq"] / s["floor"])
    assert s["rate_slope"] == pytest.approx(-1.0, abs=0.05)
    # non-finite samples are ignored, not stored
    g.update(1000, float("nan"))
    assert g.summary()["T"] == 199 * 4


def test_gap_tracker_unknown_bound():
    with pytest.raises(ValueError, match="unknown bound"):
        GapTracker(cell="c", n=4, beta=0.5, bound="bogus")


def test_report_renders(tmp_path):
    sink = MemorySink()
    gap = GapTracker(cell="dsgd/ring/ideal", n=4, beta=0.5)
    tr = Tracer()
    rec = ObsRecorder(sink, every=3, tracer=tr, gap=gap,
                      meta={"name": "demo", "algo": "dsgd"})
    for k in range(7):
        with tr.step(k):
            with tr.span("dispatch"):
                pass
            with tr.span("record"):
                with tr.span("record.sync"):
                    pass
        rec.record(k, (k + 1) * 2, None,
                   {"loss": jnp.float32(1.0 / (k + 1)),
                    "obs": {"grad_norm": jnp.float32(2.0 / (k + 1))}}, 0.01)
    rec.eval_event(6, 14, 0.5)
    rec.close()
    text = report.render(sink.events)
    assert "demo" in text
    assert "grad_norm" in text and "loss" in text
    assert "optimality gap" in text and "gap ratio" in text
    assert "phases" in text
    # the span tree: the child indented under its parent
    rows = [line.split() for line in text.splitlines()]
    names = [r[0] for r in rows if r and r[0] in
             ("dispatch", "record", "record.sync")]
    assert set(names) == {"dispatch", "record", "record.sync"}
    assert "    record.sync" in text and "  record " in text
    assert any(c in text for c in "▁▂▃▄▅▆▇█")
    # the CLI path end to end on a real file
    path = str(tmp_path / "log.jsonl")
    log = EventLog(path)
    for e in sink.events:
        log.emit(e)
    log.close()
    assert report.main([path]) == 0


def test_sparkline():
    assert report.sparkline([]) == ""
    assert report.sparkline([1.0, 1.0]) == "▁▁"
    line = report.sparkline(list(range(64)), width=8)
    assert len(line) == 8 and line[0] == "▁" and line[-1] == "█"


def test_console_quiet_and_events():
    buf = io.StringIO()
    con = Console(quiet=False, stream=buf)
    con.print("hello")
    con.event("result", algo="dsgd", grad_sq=0.125)
    out = buf.getvalue()
    assert "hello" in out
    assert "result algo=dsgd grad_sq=0.125" in out
    qbuf = io.StringIO()
    quiet = Console(quiet=True, stream=qbuf, sink=(sink := MemorySink()))
    quiet.print("nope")
    quiet.event("result", x=1)
    assert qbuf.getvalue() == ""  # silent ...
    assert sink.events == [{"event": "result", "x": 1}]  # ... but logged
    assert Console.from_argv(["--quiet"]).quiet
    assert not Console.from_argv([]).quiet


def test_obsspec_roundtrip_and_validation(tmp_path):
    from repro import exp

    # defaults elide: an obs-less spec serializes exactly as before
    assert exp.to_dict(exp.ExperimentSpec()) == {}
    sp = exp.from_dict({"obs": {"metrics": "x.jsonl", "every": 5}})
    assert sp.obs.metrics == "x.jsonl" and sp.obs.every == 5
    assert sp.obs.enabled
    assert not exp.ExperimentSpec().obs.enabled
    assert exp.from_dict(exp.to_dict(sp)) == sp
    with pytest.raises(KeyError):
        exp.from_dict({"obs": {"bogus": 1}})
    with pytest.raises(ValueError, match="obs.sink"):
        exp.build(exp.from_dict({"obs": {"metrics": "x", "sink": "bogus"}}))
    with pytest.raises(ValueError, match="unknown obs metric"):
        exp.build(exp.from_dict({"obs": {"metrics": "x",
                                         "names": "bogus"}}))
    # obs is observation-only: restore-mismatch diffs ignore it
    assert exp.diff_specs(sp, exp.ExperimentSpec()) == []


def test_exp_run_obs_end_to_end(tmp_path):
    from repro import exp

    log = str(tmp_path / "run.jsonl")
    sp = exp.from_dict({
        "model": {"kind": "logreg", "d": 8, "m": 32},
        "algorithm": {"name": "mc_dsgt", "R": 2},
        "run": {"steps": 5, "nodes": 4, "eval_every": 2},
        "obs": {"metrics": log, "every": 3},
    })
    res = exp.run(sp)
    assert len(res.history) >= 2
    events = read_events(log)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "meta" and kinds[-1] == "summary"
    assert kinds.count("step") == 5
    assert kinds.count("eval") >= 2
    meta = events[0]
    assert meta["cell"] == "mc_dsgt/sun/ideal"
    assert meta["spec_hash"] == exp.spec_hash(sp)
    stepev = next(e for e in events if e["event"] == "step")
    for name in ("grad_norm", "consensus", "mix_residual",
                 "tracker_residual", "sec", "phases"):
        assert name in stepev, name
    summ = events[-1]
    assert summ["optimality"]["gap_ratio"] is not None
    assert {"data", "dispatch", "record"} <= set(summ["phases"])
    # step 0 compiled its step program inside the dispatch span
    assert stepev["step"] == 0 and stepev["compiles"] > 0
    assert 0 in summ["phases"]["dispatch"]["compiled_steps"]
    # manifest written next to the event log, records the log + obs names
    m = exp.load_manifest(exp.manifest_path(log))
    assert m["spec_parsed"] == sp
    assert m["realized"]["event_log"] == log
    assert "grad_norm" in m["realized"]["obs_names"]


def test_train_cli_metrics_flags(tmp_path):
    from repro.launch import train

    log = str(tmp_path / "cli.jsonl")
    hist = train.main([
        "--steps", "3", "--nodes", "4", "--batch", "1", "--seq", "16",
        "--metrics", log, "--metrics-every", "2", "--quiet"])
    assert len(hist) == 3
    events = read_events(log)
    assert [e["event"] for e in events].count("step") == 3
    assert all(np.isfinite(e["loss"]) for e in events
               if e["event"] == "step")
    # --dump-config round-trips the obs section
    spec = train.main(["--metrics", "m.jsonl", "--dump-config"])
    assert spec.obs.metrics == "m.jsonl"


def test_engine_obs_unknown_name_raises():
    rule = engine.make_rule("dsgd", gamma=0.1)
    algo = alg.from_rule(rule)
    state = algo.init(jnp.zeros((N, D)))
    Ws = jnp.asarray(_sched().stacked(0, 1))
    with pytest.raises(ValueError, match="unknown obs metric"):
        algo.step(state, _host_grad, Ws, KEY, obs=("bogus",))


# ---------------------------------------------------------------------------
# The loop's spans, the compile counter and the profiler window
# ---------------------------------------------------------------------------

ARCH_STEPS = 4
RECORD_CHILDREN = ("record.telemetry", "record.sync", "record.readback")


def _arch_spec(tmp, **obs):
    from repro import exp

    return exp.ExperimentSpec(
        model=exp.ModelRef(kind="arch", arch="qwen1.5-0.5b",
                           preset="reduced"),
        data=exp.DataSpec(batch=1, seq=16),
        algorithm=exp.AlgorithmSpec(name="mc_dsgt", R=1),
        topology=exp.TopologySpec(kind="one-peer-exp"),
        run=exp.RunSpec(steps=ARCH_STEPS, nodes=2, gossip_impl="auto",
                        telemetry=str(tmp / "telemetry.json"),
                        checkpoint=str(tmp / "ck.msgpack")),
        obs=exp.ObsSpec(**obs))


def _spans_since(t0):
    return [s for s in obs_trace.spans() if s.start >= t0]


@pytest.fixture(scope="module")
def arch_run(tmp_path_factory):
    """exp.run of the arch runtime with the default ObsSpec (a telemetry
    recorder and a checkpoint, so every span has work), and its spans."""
    import time

    from repro import exp

    t0 = time.perf_counter()
    res = exp.run(_arch_spec(tmp_path_factory.mktemp("arch")), quiet=True)
    return res, _spans_since(t0)


def test_exp_run_records_every_span_every_step(arch_run):
    res, got = arch_run
    assert not res.spec.obs.enabled
    for k in range(ARCH_STEPS):
        names = sorted(s.name for s in got if s.k == k)
        assert names == sorted(("data", "dispatch", "record")
                               + RECORD_CHILDREN), k
    ck = [s for s in got if s.name == "checkpoint"]
    assert len(ck) == 1 and ck[0].k is None and ck[0].parent is None
    assert set(obs_trace.SPANS) == {s.name for s in got}


def test_spans_carry_step_and_parent(arch_run):
    _, got = arch_run
    by = {(s.name, s.k): s for s in got}
    for k in range(ARCH_STEPS):
        for name in ("data", "dispatch", "record"):
            assert by[name, k].parent is None
        rec = by["record", k]
        assert by["dispatch", k].end <= rec.start
        for name in RECORD_CHILDREN:
            child = by[name, k]
            assert child.parent == "record"
            assert rec.start <= child.start <= child.end <= rec.end
        # the children run in order: telemetry, sync, readback
        assert by["record.telemetry", k].end <= by["record.sync", k].start
        assert by["record.sync", k].end <= by["record.readback", k].start
    summ = arch_run[0].built.tracer.summary()
    assert summ["record.sync"]["parent"] == "record"
    rec = summ["record"]
    assert rec["self_sec"] == pytest.approx(
        rec["total_sec"] - sum(summ[n]["total_sec"]
                               for n in RECORD_CHILDREN), abs=1e-9)


def test_history_ready_is_the_sync_spans_end(arch_run):
    res, got = arch_run
    sync = {s.k: s.end for s in got if s.name == "record.sync"}
    assert [row["ready"] for row in res.history] == \
        [sync[k] for k in range(ARCH_STEPS)]


def test_first_step_compiles_in_its_dispatch_span(arch_run):
    _, got = arch_run
    dispatch = {s.k: s.compiles for s in got if s.name == "dispatch"}
    assert dispatch[0] >= 1
    assert all(dispatch[k] == 0 for k in range(1, ARCH_STEPS))


@pytest.fixture(scope="module")
def bare_arch_run(tmp_path_factory):
    """exp.run of the arch runtime with no recorder, as the chip cells run
    it, so the record hook reads back the loss and the consensus distance
    itself; with every step's ``float(out["loss"])`` taken after its hook
    and JAX's caches cleared first, so step 0 compiles what it uses."""
    import dataclasses
    import time

    from repro import exp

    spec = _arch_spec(tmp_path_factory.mktemp("bare"))
    spec = dataclasses.replace(spec, run=dataclasses.replace(
        spec.run, telemetry=None, checkpoint=None))
    run_loop, losses = driver.run_loop, []

    def spy_loop(*args, record, **kw):
        def spy(k, t, state, out, dt):
            row = record(k, t, state, out, dt)
            losses.append(float(out["loss"]))
            return row
        return run_loop(*args, record=spy, **kw)

    jax.clear_caches()
    t0 = time.perf_counter()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(driver, "run_loop", spy_loop)
        res = exp.run(spec, quiet=True)
    return res, losses, _spans_since(t0)


def test_record_hook_reads_back_in_one_program(bare_arch_run):
    from test_sim import eager_consensus_distance

    res, losses, got = bare_arch_run
    assert res.telemetry is None
    assert [row["loss"] for row in res.history] == losses
    assert res.history[-1]["consensus"] == pytest.approx(
        eager_consensus_distance(res.state.x), rel=1e-5)
    assert all(set(row) == {"step", "loss", "consensus", "sec", "ready"}
               for row in res.history)
    # the fused reduction is the readback's one new program
    readback = {s.k: s.compiles for s in got if s.name == "record.readback"}
    assert 1 <= readback[0] <= 2
    assert all(readback[k] == 0 for k in range(1, ARCH_STEPS))


def test_span_ring_is_bounded():
    tr = Tracer()
    for i in range(obs_trace.RING_SIZE + 5):
        with tr.span("ring"):
            pass
    got = obs_trace.spans()
    assert len(got) == obs_trace.RING_SIZE
    assert all(s.name == "ring" for s in got)
    assert tr.summary()["ring"]["count"] == obs_trace.RING_SIZE + 5


def test_summary_statistics_cover_every_call(monkeypatch):
    # 5,000 long calls, then 4,096 short ones: the median of every call
    # is the long one (a window of the last 4,096 would read the short);
    # binary fractions keep the fake clock exact
    long, short = 2.0 ** -7, 2.0 ** -10
    clock = [0.0]
    monkeypatch.setattr(obs_trace.time, "perf_counter", lambda: clock[0])
    tr = Tracer()
    for i, dur in enumerate([long] * 5000 + [short] * 4096):
        tr.k = i
        with tr.span("s"):
            clock[0] += dur
    s = tr.summary()["s"]
    assert s["count"] == 9096
    assert s["median_ms"] == 1e3 * long
    assert s["total_sec"] == 5000 * long + 4096 * short
    assert s["mean_ms"] == pytest.approx(1e3 * s["total_sec"] / 9096)
    assert (s["max_ms"], s["max_step"]) == (1e3 * long, 0)


def test_module_span_joins_the_open_spans_tracer():
    tr = Tracer()
    tr.k = 7
    with tr.span("record"):
        with obs_trace.span("record.telemetry") as child:
            pass
    assert (child.k, child.parent_name) == (7, "record")
    assert tr.summary()["record.telemetry"]["parent"] == "record"
    with pytest.raises(RuntimeError, match="outside any span"):
        obs_trace.span("record.telemetry")


def test_run_algorithm_spans_its_telemetry_under_record():
    import time

    sched = _sched()
    t0 = time.perf_counter()
    driver.run_algorithm(alg.dsgd(0.2), jnp.zeros((N, D)), _host_grad,
                         sched, 3, KEY,
                         telemetry=TelemetryRecorder(sched, wps=1, window=4))
    got = _spans_since(t0)
    for k in range(3):
        names = {s.name: s.parent for s in got if s.k == k}
        assert names == {"data": None, "dispatch": None, "record": None,
                         "record.telemetry": "record"}, k


def test_forced_recompile_is_credited_to_its_steps_dispatch():
    import time

    step = driver.bind_step(
        driver.StagedGossip("auto", None, 1, 1),
        lambda state, extra, tensors, t: (state + jnp.sum(extra), None))
    # a new input shape at step 3: one more compile, in that dispatch
    extra = lambda k: jnp.ones((4 + (k >= 3),), jnp.float32)  # noqa: E731
    c0 = obs_trace.compile_counts()["compiles"]
    t0 = time.perf_counter()
    tr = Tracer()
    driver.run_loop(step, jnp.float32(0), steps=5, wps=1, period=1,
                    extra_fn=extra, record=lambda *a: None, tracer=tr)
    dispatch = {s.k: s.compiles for s in _spans_since(t0)
                if s.name == "dispatch"}
    assert dispatch[0] >= 1 and dispatch[3] >= 1
    assert dispatch[1] == dispatch[2] == dispatch[4] == 0
    assert tr.summary()["dispatch"]["compiled_steps"] == [0, 3]
    assert obs_trace.compile_counts()["compiles"] - c0 >= 2


def _xplane_events(directory):
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)
    assert path, f"no trace under {directory}"
    data = ProfileData.from_file(path[0])
    return {plane.name: [(line.name, e.name, e.start_ns,
                          e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events]
            for plane in data.planes}


def test_spans_share_the_profilers_timeline(tmp_path):
    # the program's spans land on the host plane of a profiler trace, and
    # each step's execution lies between its dispatch start and its sync
    # end (sin marks the step's own op on the CPU's XLA threads)
    tr = Tracer()
    span = tr.span

    @jax.jit
    def step(state, x, t):
        return state + jnp.sum(jnp.sin(x)) * t, {"loss": state}

    def record(k, t, state, out, dt):
        with span("record.sync"):
            jax.block_until_ready((state, out))
        with span("record.readback"):
            return float(out["loss"])

    x = jnp.ones((64, 64), jnp.float32)
    driver.run_loop(step, jnp.float32(0), steps=2, wps=1, period=4,
                    extra_fn=lambda k: x, record=record, tracer=tr)
    jax.profiler.start_trace(str(tmp_path))
    try:
        driver.run_loop(step, jnp.float32(0), steps=3, wps=1, period=4,
                        start_step=2, extra_fn=lambda k: x, record=record,
                        tracer=tr)
    finally:
        jax.profiler.stop_trace()
    planes = _xplane_events(str(tmp_path))
    host = planes["/host:CPU"]
    names = {n for _, n, _, _ in host}
    assert {"repro/data", "repro/dispatch", "repro/record",
            "repro/record.sync", "repro/record.readback"} <= names
    assert sum(1 for _, n, _, _ in host if n == "train") == 3
    dispatch = sorted(s for _, n, s, _ in host if n == "repro/dispatch")
    sync_end = sorted(e for _, n, _, e in host if n == "repro/record.sync")
    runs = sorted(s for line, n, s, _ in host
                  if n == "wrapped_sine" and line.startswith("tf_XLA"))
    assert len(dispatch) == len(sync_end) == len(runs) == 3
    for a, r, b in zip(dispatch, runs, sync_end):
        assert a <= r <= b


def test_profiler_traces_steady_steps_only(tmp_path):
    # the first step compiles; the trace opens after the first step that
    # compiled nothing and holds the `profile_steps` steps after it
    import time

    from repro import exp

    sp = exp.from_dict({
        "model": {"kind": "logreg", "d": 8, "m": 32},
        "algorithm": {"name": "dsgd"},
        "run": {"steps": 7, "nodes": 4},
        "obs": {"profile_dir": str(tmp_path / "prof"), "profile_steps": 2},
    })
    t0 = time.perf_counter()
    res = exp.run(sp)
    got = _spans_since(t0)
    compiled = {}
    for s in got:
        compiled[s.k] = compiled.get(s.k, 0) + s.compiles
    assert compiled[0] > 0
    clean = min(k for k, n in compiled.items() if k is not None and n == 0)
    prof = res.built.obs.profiler
    assert (prof.first, prof.last) == (clean + 1, clean + 2)
    assert all(compiled[k] == 0 for k in (prof.first, prof.last))
    steps = [n for _, n, _, _ in _xplane_events(str(tmp_path / "prof"))
             ["/host:CPU"] if n == "train"]
    assert len(steps) == 2
