"""repro.sim tests: mobility schedules, channel faults, weight repair,
realized-plan lowering, and mixing telemetry.

Covers the ISSUE acceptance path end to end: seed-stream determinism under
out-of-order queries, Assumption 3 on repaired matrices for every channel
model (plus the documented row-stochastic fallback for directed masks),
degraded-plan mixing exact against the reconstructed dense matrices on
both runtimes, and the 16-node geometric-mobility resilience demo under
20% iid link drop."""

import json
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg, driver, gossip, topology as topo
from repro.sim import telemetry as sim_telemetry
from repro.sim import (BernoulliDropChannel, GilbertElliottChannel,
                       LinkLatencyModel, NodeChurn, StragglerInjection,
                       TelemetryRecorder, combined_mask,
                       consensus_distance, empirical_effective_diameter,
                       random_geometric_schedule, random_waypoint_schedule,
                       realize_weight_schedule, repair_weights,
                       unit_disk_adjacency, windowed_spectral_gap)

N = 12

CHANNEL_MODELS = {
    "bernoulli": BernoulliDropChannel(0.3, seed=3),
    "gilbert_elliott": GilbertElliottChannel(0.2, p_good=0.3, seed=4),
    "churn": NodeChurn(0.2, seed=5),
    "straggler": StragglerInjection(0.3, seed=6),
}


def _matching_ws(n=N, horizon=16, seed=0):
    return gossip.schedule_from_topology(
        topo.resampled_matching_schedule(n, seed=seed), horizon=horizon)


# ---------------------------------------------------------------------------
# Mobility schedules
# ---------------------------------------------------------------------------

def test_unit_disk_adjacency_matches_pairwise_distance():
    rng = np.random.default_rng(0)
    pos = rng.random((N, 2))
    adj = unit_disk_adjacency(pos, 0.4)
    assert np.array_equal(adj, adj.T) and adj.diagonal().all()
    for i in range(N):
        for j in range(N):
            if i != j:
                d = np.linalg.norm(pos[i] - pos[j])
                assert adj[i, j] == (d <= 0.4)


def test_waypoint_mobility_is_temporally_correlated():
    """Positions move continuously: per-round displacement is bounded by
    the leg length / leg_rounds, unlike the iid geometric draw."""
    sched = random_waypoint_schedule(N, leg_rounds=8, seed=1)
    for t in range(20):
        step = np.abs(sched.positions(t + 1) - sched.positions(t)).max()
        assert step <= np.sqrt(2) / 8 + 1e-12
    # geometric teleports: same bound would a.s. fail somewhere
    geo = random_geometric_schedule(N, seed=1)
    steps = [np.abs(geo.positions(t + 1) - geo.positions(t)).max()
             for t in range(20)]
    assert max(steps) > np.sqrt(2) / 8


def test_mobility_feeds_weight_schedule_and_planner():
    for sched in (random_geometric_schedule(N, 0.45, seed=0),
                  random_waypoint_schedule(N, 0.45, seed=0)):
        assert sched.period is None
        ws = gossip.schedule_from_topology(sched, horizon=6)
        plan = ws.plan(0, 6)  # validates vs dense + Assumption 3
        assert plan.period == 6


# ---------------------------------------------------------------------------
# Satellite: seed-stream determinism under out-of-order / repeated queries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,stream", [
    ("resampled-matching", topo.resampled_matching_schedule(N, seed=9)),
    ("geometric", random_geometric_schedule(N, seed=9)),
    ("waypoint", random_waypoint_schedule(N, seed=9)),
])
def test_schedule_determinism_out_of_order(name, stream):
    ts = list(range(24))
    in_order = {t: np.array(stream(t)) for t in ts}
    kinds = {t: stream.structure(t).kind for t in ts}
    shuffled = ts[:]
    random.Random(7).shuffle(shuffled)
    for t in shuffled + shuffled:  # out-of-order AND repeated
        assert np.array_equal(stream(t), in_order[t]), (name, t)
        assert stream.structure(t).kind == kinds[t], (name, t)


@pytest.mark.parametrize("name", sorted(CHANNEL_MODELS))
def test_channel_mask_determinism_out_of_order(name):
    model = CHANNEL_MODELS[name]
    ts = list(range(24))
    in_order = {t: model.mask(t, N) for t in ts}
    shuffled = ts[:]
    random.Random(3).shuffle(shuffled)
    for t in shuffled + shuffled:
        assert np.array_equal(model.mask(t, N), in_order[t]), (name, t)


def test_gilbert_elliott_is_bursty():
    """Bad states persist: consecutive-round state agreement beats the iid
    rate, and the chain still visits both states."""
    ge = GilbertElliottChannel(0.15, p_good=0.2, seed=11, block=64)
    states = np.stack([ge.bad_state(t, N) for t in range(60)])
    frac_bad = states.mean()
    assert 0.05 < frac_bad < 0.9
    same = (states[1:] == states[:-1]).mean()
    iid_same = frac_bad ** 2 + (1 - frac_bad) ** 2
    assert same > iid_same + 0.05


# ---------------------------------------------------------------------------
# Satellite: fault repair validity (Assumption 3 / row-stochastic fallback)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CHANNEL_MODELS))
@pytest.mark.parametrize("base", ["matching", "mobility", "sun"])
def test_repaired_matrices_satisfy_assumption3(name, base):
    """For every channel model x base topology, each realized round passes
    check_assumption3 on its realized sparsity pattern."""
    if base == "matching":
        ideal = _matching_ws()
    elif base == "mobility":
        ideal = gossip.schedule_from_topology(
            random_geometric_schedule(N, 0.5, seed=2), horizon=16)
    else:
        ideal = gossip.theorem3_weight_schedule(N, 0.75)
    realized = realize_weight_schedule(ideal, [CHANNEL_MODELS[name]],
                                       rounds=16)
    for t in range(16):
        W = realized(t)
        adj = np.abs(W) > 1e-12
        np.fill_diagonal(adj, True)
        assert np.array_equal(W, W.T), "repair must stay symmetric"
        gossip.check_assumption3(W, adj)


def test_repair_directed_mask_is_row_stochastic_fallback():
    """A directed (asymmetric) drop breaks double stochasticity: rows still
    sum to 1 (each node still takes a convex combination of what it
    received) but columns need not — the documented fallback, and why
    realize_weight_schedule symmetrizes every mask."""
    W = gossip.metropolis_weights(topo.ring_graph(6))
    mask = np.ones((6, 6), dtype=bool)
    mask[0, 1] = False  # 1 -> 0 lost, 0 -> 1 survives
    repaired = repair_weights(W, mask)
    ones = np.ones(6)
    np.testing.assert_allclose(repaired @ ones, ones, atol=1e-12)
    assert abs((ones @ repaired)[1] - 1.0) > 1e-3  # column sums broken
    with pytest.raises(AssertionError):
        gossip.check_assumption3(repaired)
    # the symmetrized mask restores Assumption 3
    sym = repair_weights(W, mask & mask.T)
    gossip.check_assumption3(sym)


def test_repair_identities():
    W = gossip.metropolis_weights(topo.sun_shaped_graph(8, [0, 1]))
    full = np.ones((8, 8), dtype=bool)
    np.testing.assert_array_equal(repair_weights(W, full), W)
    none = np.zeros((8, 8), dtype=bool)
    np.testing.assert_array_equal(repair_weights(W, none), np.eye(8))


def test_combined_mask_symmetrizes_and_keeps_diagonal():
    m = combined_mask([CHANNEL_MODELS["bernoulli"],
                       CHANNEL_MODELS["churn"]], 3, N)
    assert np.array_equal(m, m.T) and m.diagonal().all()


# ---------------------------------------------------------------------------
# Realized plans: lowering selection + exactness
# ---------------------------------------------------------------------------

def test_degraded_matching_lowers_to_matching_and_empty():
    """Partially dropped matchings keep the one-peer lowering (perm fixes
    the unmatched nodes); fully dropped rounds lower to free empty
    rounds."""
    ideal = _matching_ws(horizon=12)
    realized = realize_weight_schedule(
        ideal, [BernoulliDropChannel(0.5, seed=1)], rounds=12)
    plan = realized.plan(0, 12)
    assert set(plan.kinds) <= {"matching", "empty"}
    assert "matching" in plan.kinds
    partial = [rd for rd in plan.rounds if rd.kind == "matching"
               and (rd.perm == np.arange(N)).any()
               and (rd.perm != np.arange(N)).any()]
    assert partial, "50% drop should leave some partial matchings"
    for rd in partial:
        fixed = rd.perm == np.arange(N)
        assert np.all(rd.w_peer[fixed] == 0.0)
    # total loss => identity round => empty
    dead = realize_weight_schedule(
        ideal, [BernoulliDropChannel(1.0, seed=1)], rounds=4)
    assert set(dead.plan(0, 4).kinds) == {"empty"}


@pytest.mark.parametrize("name", sorted(CHANNEL_MODELS))
def test_degraded_plan_mixing_bitexact_vs_reconstructed_dense(name):
    """Per round: mixing through the structured lowering == mixing with the
    round's reconstructed dense matrix, bit for bit (matching base, so the
    lowerings exercised are matching/empty)."""
    ideal = _matching_ws()
    realized = realize_weight_schedule(ideal, [CHANNEL_MODELS[name]],
                                       rounds=16)
    plan = realized.plan(0, 16)
    assert set(plan.kinds) <= {"matching", "empty"}
    tensors = jax.tree.map(jnp.asarray, plan.tensors())
    mixer = alg.make_plan_mixer(plan, mode="static")
    x = jax.random.normal(jax.random.key(0), (N, 7))
    for t, rd in enumerate(plan.rounds):
        got = np.asarray(mixer(tensors, t, 1, x))
        want = np.asarray(alg.mix(jnp.asarray(rd.as_dense(), jnp.float32), x))
        np.testing.assert_array_equal(got, want, err_msg=f"round {t}")


def test_realized_window_planned_equals_dense_multi_consensus():
    """Whole realized window through the plan dispatcher == the dense
    matrix-product reference (the lowering-correctness acceptance check on
    the host runtime)."""
    ideal = gossip.schedule_from_topology(
        random_geometric_schedule(16, 0.45, seed=0), horizon=12)
    realized = realize_weight_schedule(
        ideal, [BernoulliDropChannel(0.2, seed=1),
                GilbertElliottChannel(0.1, seed=2)], rounds=12)
    plan = realized.plan(0, 12)
    tree = {"a": jax.random.normal(jax.random.key(1), (16, 5)),
            "b": jax.random.normal(jax.random.key(2), (16, 3, 2))}
    want = alg.multi_consensus(jnp.asarray(realized.stacked(0, 12)), tree)
    mixer = alg.make_plan_mixer(plan, mode="static")
    got = mixer(jax.tree.map(jnp.asarray, plan.tensors()), 0, 12, tree)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert float(jnp.abs(w - g).max()) < 1e-5


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------

def test_consensus_distance_zero_iff_consensus():
    x = jnp.ones((4, 3))
    assert consensus_distance({"w": x}) == 0.0
    x2 = x.at[0].set(2.0)
    assert consensus_distance({"w": x2}) > 0.5


def eager_consensus_distance(x):
    """The per-leaf eager formula, one host sync per leaf: the oracle of
    the fused ``consensus_distance``."""
    tot = 0.0
    for leaf in jax.tree.leaves(x):
        arr = jnp.asarray(leaf)
        xb = jnp.mean(arr, axis=0, keepdims=True)
        tot += float(jnp.sum((arr - xb) ** 2))
    return tot ** 0.5


def _ranked_tree(n, dtype, seed=0):
    """Leaves of rank 1 to 4, off-centre so the mean matters."""
    ks = jax.random.split(jax.random.key(seed), 4)
    shapes = {"s": (n,), "b": (n, 40), "w": (n, 16, 24), "k": (n, 3, 4, 5)}
    return {name: (3.0 + jax.random.normal(k, shp)).astype(dtype)
            for k, (name, shp) in zip(ks, shapes.items())}


@pytest.mark.parametrize("n", [2, 8])
@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2.0 ** -8)])
def test_consensus_distance_matches_eager_formula(n, dtype, rtol):
    # bf16: within one bf16 epsilon (the fused program may keep the
    # square's excess precision inside the fusion)
    x = _ranked_tree(n, dtype)
    want = eager_consensus_distance(x)
    assert want > 0
    assert consensus_distance(x) == pytest.approx(want, rel=rtol)
    assert sim_telemetry.consensus_sums(x).shape == (len(x),)


def test_consensus_distance_mixed_dtypes():
    x = {"a": _ranked_tree(4, jnp.float32)["w"],
         "b": _ranked_tree(4, jnp.bfloat16, seed=1)["b"]}
    assert sim_telemetry.consensus_sums(x).dtype == jnp.float32
    assert consensus_distance(x) == pytest.approx(
        eager_consensus_distance(x), rel=2.0 ** -8)


def test_windowed_spectral_gap_and_diameter():
    n = 8
    J = np.ones((n, n)) / n
    assert abs(windowed_spectral_gap(np.stack([J])) - 1.0) < 1e-9
    eye = np.stack([np.eye(n)])
    assert abs(windowed_spectral_gap(eye) - 0.0) < 1e-9
    comp = np.ones((1, n, n), dtype=bool)
    assert empirical_effective_diameter(comp) == 1
    assert empirical_effective_diameter(np.eye(n, dtype=bool)[None]) is None


def test_telemetry_recorder_and_json_dump(tmp_path):
    ideal = _matching_ws(n=8, horizon=24, seed=1)
    realized = realize_weight_schedule(
        ideal, [BernoulliDropChannel(0.2, seed=2)], rounds=24)
    rec = TelemetryRecorder(realized, wps=2, window=8)

    class S:
        x = jnp.ones((8, 3)).at[0].set(0.0)

    entry = rec.record(3, 12, S(), {"loss": jnp.float32(1.5)}, 0.01)
    assert entry["loss"] == 1.5 and entry["window"] == [4, 12]
    assert entry["consensus"] > 0 and 0.0 <= entry["spectral_gap"] <= 1.0
    assert sum(entry["kinds"].values()) == 8
    path = str(tmp_path / "telem.json")
    rec.dump(path)
    blob = json.load(open(path))
    assert set(blob) == {"fields", "history"}
    assert blob["history"][0]["step"] == 3
    assert "eff_diameter" in blob["fields"]


# ---------------------------------------------------------------------------
# End-to-end: resilience demo + both runtimes
# ---------------------------------------------------------------------------

def test_e2e_mobility_linkdrop_resilience_host():
    """Acceptance: 16-node geometric mobility under 20% iid link drop —
    mc_dsgt and gt_local still decrease the loss, and the telemetry
    history reports realized effective diameter and consensus distance."""
    n, d = 16, 32
    rng = np.random.default_rng(0)
    centers = jnp.asarray(rng.normal(size=(n, d)) * 2.0)

    def grad_fn(xs, key):
        return xs - centers + 0.3 * jax.random.normal(key, xs.shape)

    def eval_fn(xb):
        return jnp.sum((xb - centers.mean(0)) ** 2)

    ideal = gossip.schedule_from_topology(
        random_geometric_schedule(n, 0.45, seed=0), horizon=200)
    realized = realize_weight_schedule(
        ideal, [BernoulliDropChannel(0.2, seed=1)], rounds=200)
    for name, algo in [("mc_dsgt", alg.mc_dsgt(0.2, R=2)),
                       ("gt_local", alg.gt_local(0.2))]:
        steps = 160 // algo.weights_per_step
        telem = TelemetryRecorder(realized, wps=algo.weights_per_step)
        _, hist = alg.run(algo, jnp.zeros((n, d)), grad_fn, realized, steps,
                          jax.random.key(0), eval_fn=eval_fn,
                          eval_every=max(1, steps - 1), telemetry=telem)
        first, last = float(hist[0][1]), float(hist[-1][1])
        assert last < first, (name, first, last)
        diams = [e["eff_diameter"] for e in telem.history
                 if e["eff_diameter"] is not None]
        assert diams, "telemetry must report realized effective diameters"
        assert all(e["consensus"] >= 0 for e in telem.history)


def test_train_cli_mobility_linkdrop_auto_matches_dense(tmp_path):
    """Dist runtime: --gossip-impl auto == dense, step for step, on the
    realized (mobility + 20% drop) schedule; the telemetry JSON lands on
    disk with the realized-window fields."""
    from repro.launch.train import main as train_main
    telem_path = str(tmp_path / "telem.json")
    base = ["--arch", "qwen1.5-0.5b", "--preset", "reduced", "--steps", "2",
            "--nodes", "4", "--batch", "1", "--seq", "16",
            "--topology", "geometric-mobility", "--link-drop", "0.2"]
    dense = train_main(base + ["--gossip-impl", "dense",
                               "--telemetry", telem_path])
    auto = train_main(base + ["--gossip-impl", "auto"])
    assert len(dense) == len(auto) == 2
    for hd, ha in zip(dense, auto):
        np.testing.assert_allclose(hd["loss"], ha["loss"], rtol=2e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(hd["consensus"], ha["consensus"],
                                   atol=1e-3)
    blob = json.load(open(telem_path))
    for e in blob["history"]:
        assert {"consensus", "spectral_gap", "eff_diameter",
                "kinds"} <= set(e)


def test_train_cli_churn_straggler_burst_smoke():
    """The full degradation stack (bursty loss + churn + stragglers) runs
    end to end through the CLI and keeps the loss finite."""
    from repro.launch.train import main as train_main
    hist = train_main(["--arch", "qwen1.5-0.5b", "--preset", "reduced",
                       "--steps", "2", "--nodes", "4", "--batch", "1",
                       "--seq", "16", "--topology", "waypoint-mobility",
                       "--burst-loss", "0.1", "--churn", "0.1",
                       "--straggler", "0.2", "--gossip-impl", "auto"])
    assert len(hist) == 2
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_run_algorithm_auto_equals_dense_on_ideal_schedules():
    """The new host plan path (driver.run_algorithm gossip_impl='auto')
    reproduces the dense path on the structured paper schedules too."""
    n, d = 8, 8
    centers = jnp.asarray(np.random.default_rng(0).normal(size=(n, d)))

    def grad_fn(xs, key):
        return xs - centers + 0.1 * jax.random.normal(key, xs.shape)

    def eval_fn(xb):
        return jnp.sum((xb - centers.mean(0)) ** 2)

    from repro import optim
    sched = gossip.theorem3_weight_schedule(n, 0.75)
    for algo in (alg.dsgd(0.2), alg.mc_dsgt(0.2, R=2),
                 # regression: the plan path must honor the local-optimizer
                 # hook, not silently fall back to the raw update
                 alg.dsgd(0.2, local_opt=optim.adam()),
                 alg.local_sgd(0.2, local_opt=optim.momentum())):
        _, hd = driver.run_algorithm(algo, jnp.zeros((n, d)), grad_fn, sched,
                                     6, jax.random.key(0), eval_fn=eval_fn)
        _, ha = driver.run_algorithm(algo, jnp.zeros((n, d)), grad_fn, sched,
                                     6, jax.random.key(0), eval_fn=eval_fn,
                                     gossip_impl="auto")
        for (t1, e1), (t2, e2) in zip(hd, ha):
            assert t1 == t2
            np.testing.assert_allclose(float(e1), float(e2), rtol=1e-4,
                                       atol=1e-6)
