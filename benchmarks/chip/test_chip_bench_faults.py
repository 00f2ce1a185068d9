"""``correct`` comes out false when the timed path is broken, and when the
control (the reference one precision step down) stands in the program's
place: on the CPU at the reduced preset, with each cell's own limits.
This skips the harness's look for a chip and drives the rest of a run."""

import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

import calibrate  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402

BENCH = harness.load_json(ROOT, "BENCHMARK.json")
CELL = "qwen05b.n2.s128"
SEED = 2 ** 31 + 23


@pytest.mark.parametrize("fault", [f for f in faults.FAULTS
                                   if faults.applies(f, {})])
def test_a_planted_fault_makes_the_run_incorrect(fault):
    with faults.planted(fault):
        out = harness.run_cell(BENCH, CELL, seed=SEED, seconds=0.3,
                               trace=False, t0=time.perf_counter(),
                               preset="reduced", log=lambda obj: None,
                               model_wrap=faults.model_wrap(fault))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_a_link_drop_left_out_is_read_by_the_spread_of_the_trackers():
    # the lossy cell waits for its chip readings outside BENCHMARK.json
    # (its files are kept); the readings that decide ``correct``, of the
    # first steps only: the Pallas kernel runs interpreted on the CPU
    cell = {"name": "whisper.n8.waypoint.pallas", "config": "whisper-tiny",
            "traffic": "waypoint.n8.r2.b1.s448.pallas", "chips": 1}
    bench = dict(BENCH, workloads=BENCH["workloads"] + [cell])
    summary = calibrate.calibrate(bench, cell["name"], seeds=[SEED],
                                  control_seeds=[], fault_seeds=[SEED],
                                  faults_to_plant=["no_link_drop"],
                                  preset="reduced", log=lambda s: None)
    sound, fault = summary["program"], summary["no_link_drop"]
    assert fault["spread_gap"] > 1e3 * sound["spread_gap"]
    assert fault["spread_gap"] > harness.load_json(
        HERE, "limits", "whisper.n8.onepeer.json")["spread_gap"]


def test_the_control_fails_the_cells_limits():
    summary = calibrate.calibrate(BENCH, CELL, seeds=[], control_seeds=[SEED],
                                  fault_seeds=[], faults_to_plant=[],
                                  preset="reduced", log=lambda s: None)
    limits = harness.load_json(HERE, "limits", CELL + ".json")
    assert any(summary["control"][k] > limits[k] for k in limits)
