"""What a configuration's own files tell the harness: its weight rule, its
FLOP count and its size keys, found under the root the harness is given.

The benchmark's cells read as they did before configurations could state
these (weights bit for bit, the same FLOPs and positions a step). A
mixture-of-experts configuration kept in ``testdata/`` -- the program's
granite-moe-3b-a800m at its reduced preset, not a cell -- runs through
``run_cell`` on the CPU with ``correct`` true, and false with a planted
fault; and a leaf that no rule places stops a run before it compiles."""

import hashlib
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

import calibrate  # noqa: E402
import counting  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402
import inputs  # noqa: E402

BENCH = harness.load_json(ROOT, "BENCHMARK.json")
TESTDATA = os.path.join(HERE, "testdata")
MOE = {"name": "granite-moe.n2.s32", "config": "granite-moe-3b-a800m",
       "traffic": "onepeer.n2.r1.b1.s32", "chips": 1}
MOE_BENCH = dict(BENCH, workloads=[MOE])
SEED = 2 ** 31 + 29

# sha256 over (key path, bytes) of every leaf of the weights at the
# reduced preset, as the harness drew them before a configuration could
# state its own rule
WEIGHTS_DIGEST = {
    ("qwen05b.n2.s128", 0):
        "8c57bded2307a43bd694cc52d008ffa942e0f4c2b7eaf0dc47a26c9fa18172c0",
    ("qwen05b.n2.s128", 2 ** 31 + 7):
        "258ecd6470334fa814b94a9d3a9046b1a52f706aeccdc0734a45a7697981785c",
    ("whisper.n8.onepeer", 0):
        "027785b0e1d107650ed132d392efc3080f16dd0f88ea99eb190a0cade9a2dd6c",
    ("whisper.n8.onepeer", 2 ** 31 + 7):
        "6af6c1ff85be87c4a6afceb5ae46dcdb6ff1ce4c32b0e48958c446b735f96876",
}


@pytest.mark.parametrize("workload,seed", sorted(WEIGHTS_DIGEST))
def test_the_cells_weights_are_drawn_as_before(workload, seed):
    cell = harness.find_cell(BENCH, workload)
    _, shapes = harness.scenario(cell.cfg, cell.traffic, seed, "reduced")
    weights = inputs.make_weights(shapes, seed, cell.rule)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(weights)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_DIGEST[workload, seed]


@pytest.mark.parametrize("workload,flops,positions", [
    ("qwen05b.n2.s128", 722158485504, 256),
    ("whisper.n8.onepeer", 3321702383616, 31168),
])
def test_the_cells_count_as_before(workload, flops, positions):
    cell = harness.find_cell(BENCH, workload)
    assert counting.step_flops(cell.cfg, cell.traffic, cell.model) == flops
    assert counting.positions_per_step(cell.cfg, cell.traffic) == positions


def test_a_modules_table_comes_before_the_built_in_rule():
    key = jax.tree_util.DictKey
    rule = inputs.WeightRule({"moe/wo": (-2,), "gain": 3.0,
                              "fixed": lambda shape, k: jnp.arange(shape[0])},
                             origin="a test's table")
    assert rule.place((key("moe"), key("wo")), (4, 8, 16)) == (-2,)
    assert rule.place((key("attn"), key("wo")), (4, 8, 16)) == (-3, -2)
    assert rule.place((key("mlp"), key("wo")), (8, 16)) == (-2,)
    got = rule.draw((key("gain"),), (2,), jax.random.key(0))
    np.testing.assert_array_equal(got, [3.0, 3.0])
    got = rule.draw((key("fixed"),), (3,), jax.random.key(0))
    np.testing.assert_array_equal(got, [0, 1, 2])
    w = rule.draw((key("moe"), key("wo")), (4, 400, 64), jax.random.key(1))
    assert abs(float(jnp.std(w)) * 400 ** 0.5 - 1) < 0.02


def _moe_run(**kw):
    # a step takes 0.6-2 s on a loaded CPU: the window holds a few
    return harness.run_cell(MOE_BENCH, MOE["name"], seed=SEED, seconds=4.0,
                            trace=False, t0=time.perf_counter(),
                            preset="reduced", root=TESTDATA,
                            log=lambda obj: None, **kw)


def test_a_mixture_of_experts_from_testdata_runs_correct():
    out = _moe_run()
    assert out["correct"] is True
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert set(out["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_planted_fault_fails_the_mixture_of_experts(fault):
    with faults.planted(fault):
        out = _moe_run(model_wrap=faults.model_wrap(fault))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_the_control_fails_the_mixture_of_experts():
    summary = calibrate.calibrate(MOE_BENCH, MOE["name"], seeds=[],
                                  control_seeds=[SEED], fault_seeds=[],
                                  faults_to_plant=[], preset="reduced",
                                  root=TESTDATA, log=lambda s: None)
    limits = harness.find_cell(MOE_BENCH, MOE["name"], TESTDATA).limits
    assert any(summary["control"][k] > limits[k] for k in limits)


def test_a_leaf_no_rule_places_stops_the_run_before_it_compiles(tmp_path):
    # the testdata configuration without its table: the built-in rule has
    # no entry for the router
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(os.path.join(TESTDATA, sub), tmp_path / sub)
    module = tmp_path / "configs" / (MOE["config"] + ".py")
    table = 'WEIGHTS = {"router": (-2,), "moe/wo": (-2,)}'
    assert table in module.read_text()
    module.write_text(module.read_text().replace(table, "WEIGHTS = {}"))
    harness.Compiles.watch()
    before = harness.Compiles.count
    with pytest.raises(ValueError) as err:
        harness.run_cell(MOE_BENCH, MOE["name"], seed=SEED, seconds=1.0,
                         trace=False, t0=time.perf_counter(),
                         preset="reduced", root=str(tmp_path),
                         log=lambda obj: None)
    assert "units/0_moe/moe/router" in str(err.value)
    assert str(module) in str(err.value)
    assert harness.Compiles.count == before


def test_the_reference_routes_as_the_program_does_with_fewer_experts_chosen():
    # the reduced preset chooses all 4 experts; here each token takes 2
    # of 4, with no token over capacity
    import dataclasses

    from repro import configs
    from repro.models import moe

    cfg = dataclasses.replace(configs.get("granite-moe-3b-a800m").reduced(),
                              experts_per_token=2)
    cell = harness.find_cell(MOE_BENCH, MOE["name"], TESTDATA)
    shapes = jax.eval_shape(
        lambda k: {"moe": moe.init_moe(k, cfg, jnp.float32)},
        jax.random.key(0))
    p = inputs.make_weights(shapes, SEED, cell.rule)["moe"]
    x = jax.random.normal(jax.random.key(SEED), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, aux = moe.apply_moe(p, x, cfg)
        ref, balance = cell.model.moe(p, x, 2)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(aux, balance, rtol=1e-5)
