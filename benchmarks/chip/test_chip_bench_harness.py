"""The harness end to end on the CPU at the reduced preset, through its
internal entry: the result's keys, the metric names, the checks that
decide ``correct``, and the command's refusal of a machine with no TPU."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import trace_reduce  # noqa: E402

BENCH = harness.load_json(ROOT, "BENCHMARK.json")
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
SEED = 2 ** 31 + 11


def _declared(kind):
    return {m["name"]: m for m in BENCH[kind]}


def _run(workload, trace=False, **kw):
    return harness.run_cell(BENCH, workload, seed=SEED, seconds=0.5,
                            trace=trace, t0=time.perf_counter(),
                            preset="reduced", log=lambda obj: None, **kw)


@pytest.fixture(scope="module")
def qwen_run():
    return _run("qwen05b.n2.s128")


def test_result_keys_and_metrics(qwen_run):
    out = qwen_run
    assert set(out) == KEYS
    assert list(out)[-1] == "checks"
    declared = _declared("end_to_end")
    assert set(out["metrics"]) == set(declared)
    for name, m in out["metrics"].items():
        assert m["unit"] == declared[name]["unit"]
        assert m["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] > 0 and out["failed"] == 0


def test_reference_stream_draws_the_programs_batches():
    from repro import configs
    from repro.data.synthetic import token_stream_for

    for arch in ("qwen1.5-0.5b", "whisper-tiny"):
        cfg = configs.get(arch).reduced()
        seed = SEED + 5
        prog = token_stream_for(cfg, 3, 2, 1, 16, seed=seed)
        ref = harness.stream_for(cfg, {"nodes": 3, "R": 2, "batch": 1,
                                       "seq": 16}, seed)
        for k in (0, 1, 7):
            a, b = prog.batch_at(k), ref.batch_at(k)
            assert set(a) == set(b)
            np.testing.assert_array_equal(a["tokens"], b["tokens"])
            if "frames" in a:
                np.testing.assert_allclose(a["frames"], b["frames"],
                                           rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_reference_gossip_weights_are_the_realized_schedules(workload):
    # built from the traffic file by the benchmark's own topology and
    # channel readers, the weights are those the program realized
    entry = harness.bench_entry(BENCH, workload)
    cfg = harness.load_json(HERE, "configs", entry["config"] + ".json")
    traffic = harness.load_json(HERE, "traffic", entry["traffic"] + ".json")
    built, _ = harness.scenario(cfg, traffic, SEED, preset="reduced")
    sched, ref = built.schedule, harness.weights_fn(traffic, SEED)
    for t in range(4 * traffic["R"] * 4):
        got = np.asarray(sched.stacked(t % sched.period, 1)[0], np.float64)
        # the program stages its weights in float32
        np.testing.assert_allclose(got, ref(t), rtol=0, atol=1e-7)


def test_the_configurations_overrides_reach_the_program():
    cell = harness.find_cell(BENCH, "qwen05b.n2.s128")
    cfg, traffic = cell.cfg, cell.traffic
    plan = harness.Plan(seconds=0, trace=False, check_only=True)
    spec = harness.make_spec(cfg, traffic, SEED, "reduced")
    built, _ = harness.drive(spec, cfg, traffic, plan, preset="reduced",
                             rule=cell.rule)
    assert cfg["overrides"] == {"rope_theta": 1e6}
    assert built.cfg.rope_theta == 1e6 == cfg["rope_theta"]


def test_program_agrees_with_reference_on_the_cpu(qwen_run):
    checks = qwen_run["checks"]
    assert set(checks) == {"loss_gap", "grad_gap", "change_gap"}
    assert qwen_run["correct"]
    for c in checks.values():
        assert c["value"] < c["limit"]


def _recorded():
    return trace_reduce.load_json_events(
        os.path.join(HERE, "testdata", "trace_events.json"))


def test_traced_run_reads_the_per_layer_metrics(monkeypatch):
    # the CPU has no device plane: read a recorded TPU trace instead
    events = _recorded()
    monkeypatch.setattr(trace_reduce, "load", lambda path: events)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "recorded")
    out = _run("qwen05b.n2.s128", trace=True,
               peaks=harness.peaks_for("TPU v5 lite"))
    assert set(out) == KEYS | {"breakdown"}
    declared = _declared("per_layer")
    assert set(out["metrics"]) <= set(declared)
    for name in ("step.mfu", "step.grad_ms", "mix.ms", "device.idle_share"):
        assert out["metrics"][name]["value"] > 0
    # declared for the Pallas cell only
    assert "kernel.gossip_roofline" not in out["metrics"]
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    bd = out["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_kernel_roofline_reader():
    cfg = harness.load_json(HERE, "configs", "whisper-tiny.json")
    traffic = harness.load_json(HERE, "traffic",
                                "waypoint.n8.r2.b1.s448.pallas.json")
    facts = {"trace": trace_reduce.reduce(_recorded()), "cfg": cfg,
             "traffic": traffic, "peaks": harness.peaks_for("TPU v5 lite"),
             "state_entries": 36_448_256}
    share = harness.read_metric("kernel.gossip_roofline", facts)
    # two calls of 13.5 ms against 2.85 ms each at 819 GB/s
    assert 15 < share < 30
    facts["trace"]["op_table"] = []
    assert harness.read_metric("kernel.gossip_roofline", facts) is None


def test_the_command_refuses_a_machine_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "qwen05b.n2.s128", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU" in p.stderr
    for line in p.stdout.splitlines():
        assert "correct" not in line


def test_every_cell_names_its_files():
    for w in BENCH["workloads"]:
        for sub, name in (("configs", w["config"] + ".json"),
                          ("configs", w["config"] + ".py"),
                          ("traffic", w["traffic"] + ".json"),
                          ("limits", w["name"] + ".json")):
            assert os.path.exists(os.path.join(HERE, sub, name)), (sub, name)
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert os.path.exists(os.path.join(HERE, "metrics",
                                               m["name"] + ".py"))
