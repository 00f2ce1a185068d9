"""The reduction from trace events to per-layer numbers, on hand-made
traces with hand-computed answers and on a recorded TPU trace."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import trace_reduce  # noqa: E402


def _ev(name, start, dur, scope=""):
    return {"name": name, "start": start, "dur": dur, "scope": scope}


def _hand_made():
    # three executions of the step at t=0, 100, 200 (two whole steps);
    # an eager readback program between steps; host events around gaps
    return {
        "devices": 1,
        "modules": [_ev("jit_core(1)", 0, 60), _ev("jit_core(1)", 100, 60),
                    _ev("jit_core(1)", 200, 60), _ev("jit_mean(2)", 70, 5),
                    _ev("jit_mean(2)", 170, 5)],
        "ops": [
            _ev("fusion.1", 0, 30, "jit(core)/obs_grad/dot"),
            _ev("fusion.2", 20, 20, "jit(core)/obs_grad/add"),  # overlaps
            _ev("custom-call.3", 40, 10, "jit(core)/obs_mix/gossip_mix"),
            _ev("fusion.4", 50, 10, "jit(core)/add"),
            _ev("reduce.5", 70, 5, "jit(mean)/reduce"),
            _ev("fusion.1", 100, 30, "jit(core)/obs_grad/dot"),
            _ev("custom-call.3", 130, 20, "jit(core)/obs_mix/gossip_mix"),
            _ev("reduce.5", 170, 5, "jit(mean)/reduce"),
            _ev("fusion.1", 200, 30, "jit(core)/obs_grad/dot"),  # after window
        ],
        "host": [_ev("record", 60, 40), _ev("block_until_ready", 75, 20),
                 _ev("seg", 0, 300)],
    }


def test_hand_made_trace():
    r = trace_reduce.reduce(_hand_made())
    assert r["step_program"] == "jit_core(1)"
    assert r["steps"] == 2 and r["window_ns"] == 200
    # busy: [0,60) + [70,75) + [100,150) + [170,175) = 60 + 5 + 50 + 5
    assert r["busy_ns"] == 120
    # obs_grad: [0,40) (two ops that overlap count once) + [100,130);
    # obs_mix: 10 + 20
    assert r["scope_ns"] == {"obs_grad": 70, "obs_mix": 30}
    table = {n: (ns, c) for n, _, ns, c in r["op_table"]}
    assert table["custom-call.3"] == (30, 2)
    assert r["top_ops"][0] == ("fusion.1", 60)
    # gaps: [60,70) [75,100) [150,170) [175,200); named by the innermost
    # host event over the middle of each
    assert r["idle_gaps"] == [("block_until_ready", 25), ("seg", 25),
                              ("seg", 20), ("record", 10)]


def test_every_obs_scope_gets_a_union():
    # a routing scope nested in obs_grad: a loop's op and its body's ops
    # nest in time and count once; no op of the window is under obs_mix
    ev = {
        "devices": 1,
        "modules": [_ev("jit_core(1)", 0, 80), _ev("jit_core(1)", 100, 80),
                    _ev("jit_core(1)", 200, 80)],
        "ops": [
            _ev("while.1", 0, 50, "jit(core)/obs_grad/obs_route/while"),
            _ev("fusion.2", 5, 15,
                "jit(core)/obs_grad/obs_route/while/body/dot_general"),
            _ev("fusion.3", 25, 20,
                "jit(core)/obs_grad/obs_route/while/body/dot_general"),
            _ev("fusion.4", 50, 20, "jit(core)/obs_grad/dot_general"),
            _ev("fusion.5", 100, 30, "jit(core)/obs_grad/obs_route/top_k"),
            _ev("fusion.6", 130, 10, "jit(core)/jobs_queue/add"),
            _ev("fusion.7", 150, 10, "jit(core)/add"),
        ],
        "host": [_ev("seg", 0, 300)],
    }
    r = trace_reduce.reduce(ev)
    # obs_route: [0,50) (the loop, its body inside) + [100,130);
    # obs_grad: [0,70) + [100,130); obs_mix: none
    assert r["scope_ns"] == {"obs_grad": 100, "obs_mix": 0, "obs_route": 80}
    rows = sum(ns for _, sc, ns, _ in r["op_table"] if "obs_route" in sc)
    assert rows == 50 + 15 + 20 + 30  # a sum of rows counts the body twice
    facts = {"trace": r}
    assert harness.read_metric("mix.ms", facts) is None
    assert harness.read_metric("step.grad_ms", facts) == 100 / 2 / 1e6


def test_the_recorded_traces_scopes_read_as_before():
    r = trace_reduce.reduce(trace_reduce.load_json_events(
        os.path.join(HERE, "testdata", "trace_events.json")))
    assert r["scope_ns"] == {"obs_grad": 94542515, "obs_mix": 40833514}


def test_too_few_step_executions_give_nothing():
    ev = _hand_made()
    ev["modules"] = ev["modules"][:1]
    assert trace_reduce.reduce(ev) is None


def _timeline_ns(intervals, lo, hi, res=1000):
    """Covered length of ``intervals`` inside [lo, hi) on a grid of
    ``res`` ns: a second way to take a union, for the recorded trace."""
    import numpy as np

    grid = np.zeros((hi - lo) // res + 1, bool)
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            grid[(s - lo) // res:(e - lo + res - 1) // res] = True
    return int(grid.sum()) * res


def test_recorded_tpu_trace():
    # one step of whisper.n8.waypoint.pallas on a TPU v5 lite (ops of
    # 0.5 ms or more, every gossip kernel event, Python host events)
    ev = trace_reduce.load_json_events(
        os.path.join(HERE, "testdata", "trace_events.json"))
    r = trace_reduce.reduce(ev)
    runs = sorted(m["start"] for m in ev["modules"]
                  if m["name"] == r["step_program"])
    lo, hi = runs[0], runs[-1]
    assert r["window_ns"] == hi - lo
    spans = [(o["start"], o["start"] + o["dur"]) for o in ev["ops"]]
    tol = 1000 * len(spans)  # a grid cell per interval edge
    assert abs(r["busy_ns"] - _timeline_ns(spans, lo, hi)) <= tol
    for sc in ("obs_grad", "obs_mix"):
        sp = [(o["start"], o["start"] + o["dur"]) for o in ev["ops"]
              if sc in o["scope"]]
        assert abs(r["scope_ns"][sc] - _timeline_ns(sp, lo, hi)) <= tol
        assert 0 < r["scope_ns"][sc] <= r["busy_ns"]
    kernel = [o for o in ev["ops"] if o["name"].startswith("gossip_mix")
              and lo <= o["start"] and o["start"] + o["dur"] <= hi]
    table = {n: (ns, c) for n, _, ns, c in r["op_table"]}
    got = sum(ns for n, (ns, c) in table.items()
              if n.startswith("gossip_mix"))
    assert len(kernel) == 2  # the x and the h stream
    assert got == sum(o["dur"] for o in kernel)
    assert r["busy_ns"] < r["window_ns"]
