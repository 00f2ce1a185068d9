"""The per-layer metrics that read the program's own spans
(``record.readback_ms``, ``driver.host_ms``): their medians on a
hand-built span log, their silence where the program records no span,
and both read from a reduced run of the harness on the CPU."""

import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import trace_reduce  # noqa: E402
from repro.obs import trace  # noqa: E402

BENCH = harness.load_json(ROOT, "BENCHMARK.json")
SPAN_METRICS = ("record.readback_ms", "driver.host_ms")
# per step k: data, dispatch, record.sync and record.readback seconds
DURS = {"data": lambda k: 0.001 * (k + 1), "dispatch": lambda k: 0.002,
        "record.sync": lambda k: 0.1, "record.readback": lambda k: 0.01 * k}


def _log(steps, t=100.0):
    """Spans of ``steps`` as the loop lays them out, from time ``t``; the
    ready stamps (each step's ``record.sync`` end)."""
    out, ready = [], []
    for k in steps:
        for name in ("data", "dispatch", "record.sync", "record.readback"):
            parent = "record" if name.startswith("record.") else None
            out.append(trace.Span(name, k, parent, t, t + DURS[name](k), 0))
            t += DURS[name](k)
            if name == "record.sync":
                ready.append(t)
        out.append(trace.Span("record", k, None, out[-2].start, t, 0))
    return out, ready


def test_medians_on_a_hand_built_span_log(monkeypatch):
    # an earlier run in the same process reused the step numbers: only
    # the spans inside the window's time are read
    earlier, _ = _log(range(8), t=10.0)
    spans, ready = _log(range(8))
    monkeypatch.setattr(trace, "spans", lambda: earlier + spans)
    # the window runs from step 2's ready stamp to step 6's: steps 3..6
    facts = {"ready": ready[2:7]}
    readback = harness.read_metric("record.readback_ms", facts)
    host = harness.read_metric("driver.host_ms", facts)
    # readback 30, 40, 50, 60 ms; data + dispatch 6, 7, 8, 9 ms
    assert readback == pytest.approx(45.0)
    assert host == pytest.approx(7.5)


def test_silent_without_the_programs_spans(monkeypatch):
    spans, ready = _log(range(4))
    facts = {"ready": ready}
    # a window with no span of the program's
    monkeypatch.setattr(trace, "spans", lambda: [])
    for name in SPAN_METRICS:
        assert harness.read_metric(name, facts) is None
    # a program that records no spans at all
    monkeypatch.delattr(trace, "spans")
    for name in SPAN_METRICS:
        assert harness.read_metric(name, facts) is None


def test_declared_for_every_cell():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SPAN_METRICS:
        m = declared[name]
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert m["moves"] == "tokens_per_s" and "workloads" not in m


def test_a_traced_run_reads_both_from_the_programs_spans(monkeypatch):
    # the CPU has no device plane: the device metrics read a recorded TPU
    # trace, the span metrics the run's own spans
    events = trace_reduce.load_json_events(
        os.path.join(HERE, "testdata", "trace_events.json"))
    monkeypatch.setattr(trace_reduce, "load", lambda path: events)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "recorded")
    out = harness.run_cell(BENCH, "qwen05b.n2.s128", seed=2 ** 31 + 13,
                           seconds=0.5, trace=True, t0=time.perf_counter(),
                           preset="reduced", log=lambda obj: None,
                           peaks=harness.peaks_for("TPU v5 lite"))
    for name in SPAN_METRICS:
        assert out["metrics"][name]["value"] > 0
        assert out["metrics"][name]["unit"] == "ms"
