"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

  figure2_mnist / figure2_covtype  — paper Figure 2 (§6): algorithm
      comparison on non-convex logistic regression, heterogeneous data,
      random sun-shaped graphs.  derived = final ||grad f||^2 ratio
      MC-DSGT / DSGD (< 1 reproduces the figure's ordering).
  table1_rate_T      — Table 1 row MC-DSGT: error ~ T^(-1/2) in the
      noise-dominated regime.  derived = fitted log-log slope.
  table1_speedup_n   — linear speedup term sigma/sqrt(nT).
      derived = error(n=4)/error(n=16) (theory: > 1 at matched T).
  theorem3_diameter  — Theorem 3: constructed effective distance == eq.(5).
      derived = max |construction - formula| over an (n, beta) grid.
  theorem4_progress  — Theorem 4 Instance 2: prog cap respected.
      derived = max prog / cap over the run (<= 1).
  kernel_*           — Pallas kernels (interpret mode) vs jnp oracle.
      derived = max |kernel - oracle|.
  compression_*      — compressed gossip (ISSUE 7): fused Pallas
      quantized_gossip_mix vs the unfused quantize-then-mix path, and
      convergence vs bandwidth per scheme (none/sign/int8) on the
      federated non-iid MC-DSGT scenario; writes BENCH_compression.json.
  engine_step_*      — throughput of the engine-built distributed step,
      one row per update rule (an ``exp.sweep`` over algorithm.name);
      also writes BENCH_engine.json.
  sim_*              — repro.sim wireless data path: mobility schedule
      resampling, channel degradation + weight repair, and gossip-plan
      restaging of the realized window; writes BENCH_sim.json.
  async_*            — overlapped gossip (ISSUE 8): step time with the
      stale-window double buffer on/off plus the jaxpr overlap proof,
      and delay ∈ {0,1,2} convergence on the Figure-2 scenario; writes
      BENCH_async.json.
  obs_*              — repro.obs measurement cost: in-jit metrics +
      recorder flushing vs the bare step (< 5% contract), and the
      telemetry per-round cache speedup; writes BENCH_obs.json.
  serve_*            — personalized fleet serving (ISSUE 10): continuous-
      batching prefill/decode throughput and p50/p95 request latency of
      repro.serve vs decode-slot count; writes BENCH_serve.json.
  roofline_summary   — reads experiments/dryrun/*.json if present.
      derived = #pairs whose dominant term is compute/memory/collective.

Scenario-parameterized benches (gossip_plan / engine_step / sim) generate
their rows from :class:`repro.exp.ExperimentSpec` grids via ``exp.sweep``
and emit through one :class:`BenchWriter`, so every BENCH_*.json shares the
schema {name, spec_hash, wall_ms, throughput, derived}.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only SUBSTR]
        [--json PATH]

With ``--json``, every family BENCH_*.json is additionally mirrored to the
repo root (the committed perf trajectory; see benchmarks/README.md for the
root-vs-baselines contract).
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ALL_ROWS = []  # every row of the run, for the top-level --json dump

# Root-canonical BENCH contract: with --json, every family artifact a
# BenchWriter dumps is ALSO written to the repo root as BENCH_<name>.json —
# the committed perf trajectory — while benchmarks/baselines/ holds the
# reference copies check_regression.py gates against.
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIRROR_TO_ROOT = False


def _emit(name: str, us_per_call: float, derived, *, spec=None,
          throughput: float | None = None) -> dict:
    """Print the CSV line and append a row in the shared BENCH schema —
    ``name``, ``spec_hash`` (the scenario's :func:`repro.exp.spec_hash`,
    None for non-spec'd micro-benches), ``wall_ms`` per call,
    ``throughput`` (calls/s), free-form ``derived``."""
    if spec is not None:
        from repro import exp
        spec_hash = exp.spec_hash(spec)
    else:
        spec_hash = None
    if throughput is None and us_per_call > 0:
        throughput = round(1e6 / us_per_call, 2)
    rec = {"name": name, "spec_hash": spec_hash,
           "wall_ms": round(us_per_call / 1000, 4),
           "throughput": throughput, "derived": derived}
    ALL_ROWS.append(rec)
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
    return rec


class BenchWriter:
    """Collects the rows of one bench family (same schema as :func:`_emit`)
    so they can be dumped to that family's BENCH_*.json artifact."""

    def __init__(self):
        self.rows = []

    def row(self, name: str, us_per_call: float, derived, *,
            spec=None, throughput: float | None = None) -> None:
        self.rows.append(_emit(name, us_per_call, derived, spec=spec,
                               throughput=throughput))

    def dump(self, path: str) -> None:
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.rows, f, indent=1)
        print(f"wrote {path}", file=sys.stderr)
        if MIRROR_TO_ROOT:
            root = os.path.join(REPO_ROOT, os.path.basename(path))
            with open(root, "w") as f:
                json.dump(self.rows, f, indent=1)
            print(f"wrote {root}", file=sys.stderr)


def record(name: str, us_per_call: float, derived) -> None:
    _emit(name, us_per_call, derived)


def _timed(fn, *args, reps=3):
    fn(*args)  # warmup/compile
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6, out


# ---------------------------------------------------------------------------
# Figure 2
# ---------------------------------------------------------------------------

def bench_figure2(quick: bool) -> None:
    from repro.configs.logreg_paper import COVTYPE, MNIST
    from examples import paper_figure2 as f2

    steps = 160 if quick else 480
    for lc, tag in [(MNIST, "figure2_mnist"), (COVTYPE, "figure2_covtype")]:
        t0 = time.time()
        curves = f2.run_setup(lc, steps, gamma=0.5)
        us = (time.time() - t0) * 1e6
        final = {k: v[-1][1] for k, v in curves.items()}
        mc = min(v for k, v in final.items() if k.startswith("mc"))
        record(tag, us / steps, round(mc / max(final["dsgd"], 1e-12), 4))


# ---------------------------------------------------------------------------
# Table 1: rate scaling
# ---------------------------------------------------------------------------

def _run_mc(n, beta, T, gamma, R, sigma, seed=0, d=32):
    from repro.core import algorithms as alg, gossip
    rng = np.random.default_rng(seed)
    centers = jnp.asarray(rng.normal(size=(n, d)))

    def grad_fn(xs, key):
        return xs - centers + sigma * jax.random.normal(key, xs.shape)

    def eval_fn(xbar):
        return jnp.sum((xbar - centers.mean(0)) ** 2)

    sched = gossip.theorem3_weight_schedule(n, beta)
    algo = alg.mc_dsgt(gamma, R=R)
    steps = max(2, T // (2 * R))
    _, hist = alg.run(algo, jnp.zeros((n, d)), grad_fn, sched, steps,
                      jax.random.key(seed), eval_fn=eval_fn,
                      eval_every=max(1, steps - 1))
    return float(hist[-1][1])


def bench_table1_rate_T(quick: bool) -> None:
    Ts = [64, 256, 1024] if quick else [64, 256, 1024, 4096]
    n, beta, R, sigma = 8, 0.5, 2, 2.0
    errs = []
    t0 = time.time()
    for T in Ts:
        gamma = min(0.5, 2.0 / math.sqrt(T))  # ~ 1/sqrt(T) schedule
        e = np.mean([_run_mc(n, beta, T, gamma, R, sigma, seed=s)
                     for s in range(3)])
        errs.append(e)
    us = (time.time() - t0) * 1e6
    slope = np.polyfit(np.log(Ts), np.log(np.maximum(errs, 1e-12)), 1)[0]
    record("table1_rate_T", us / len(Ts), round(float(slope), 3))


def bench_table1_speedup_n(quick: bool) -> None:
    T, beta, R, sigma = 512, 0.5, 2, 2.0
    t0 = time.time()
    errs = {}
    for n in (4, 16):
        errs[n] = np.mean([_run_mc(n, beta, T, 0.05, R, sigma, seed=s)
                           for s in range(3)])
    us = (time.time() - t0) * 1e6
    record("table1_speedup_n", us / 2,
           round(errs[4] / max(errs[16], 1e-12), 3))


# ---------------------------------------------------------------------------
# Theorem 3 / Theorem 4
# ---------------------------------------------------------------------------

def bench_r_ablation(quick: bool) -> None:
    """Theorem 6 / eq. (41): the optimal consensus-round count R grows with
    1/(1-beta).  Heterogeneous-curvature quadratics (consensus error feeds
    the bias, so multi-consensus pays off) on a well- vs poorly-connected
    schedule.  derived = bestR at each beta (expected: larger at large
    beta)."""
    from repro.core import algorithms as alg, gossip
    n, d, T, sigma = 16, 16, 768, 1.0
    rng = np.random.default_rng(0)
    centers = jnp.asarray(rng.normal(size=(n, d)) * 4.0)
    hess = jnp.asarray(rng.uniform(0.2, 2.0, size=(n, d)))
    xstar = (hess * centers).mean(0) / hess.mean(0)

    def grad_fn(xs, key):
        return hess * (xs - centers) + sigma * jax.random.normal(key, xs.shape)

    def eval_fn(xb):
        return jnp.sum((xb - xstar) ** 2)

    t0 = time.time()
    gains = {}
    Rs = [1, 2]
    for beta in (0.5, 1 - 1 / n):
        sched = gossip.theorem3_weight_schedule(n, beta)
        errs = {}
        for R in Rs:
            algo = alg.mc_dsgt(0.3, R=R)
            steps = max(2, T // (2 * R))
            fin = []
            for seed in range(3):
                _, hist = alg.run(algo, jnp.zeros((n, d)), grad_fn, sched,
                                  steps, jax.random.key(seed),
                                  eval_fn=eval_fn, eval_every=max(1, steps - 1))
                fin.append(hist[-1][1])
            errs[R] = float(np.mean(fin))
        gains[beta] = errs[1] / max(errs[2], 1e-12)  # R=1 -> R=2 improvement
    us = (time.time() - t0) * 1e6
    # Theorem 6 signature: multi-consensus helps MORE on poorly connected
    # networks -> the gain ratio should exceed 1
    record("table1_R_ablation", us / (2 * len(Rs)),
           f"gainR2(beta={1 - 1 / n:.3f})={gains[1 - 1 / n]:.2f}x"
           f"|gainR2(0.5)={gains[0.5]:.2f}x")


def bench_theorem3(quick: bool) -> None:
    from repro.core import topology as topo
    t0 = time.time()
    worst = 0
    cases = 0
    for n in (8, 16, 32):
        for bfrac in (0.0, 0.3, 0.6, 0.9, 1.0):
            beta = bfrac * (1 - 1 / n)
            size = max(1, math.ceil(n / 4))
            I1 = tuple(range(size))
            I2 = tuple(range(n - size, n))
            sched = topo.sun_shaped_schedule(n, beta, avoid=I1 + I2)
            got = topo.effective_distance(sched, I1, I2, period=sched.period)
            want = topo.theorem3_distance_formula(n, beta, size, size)
            worst = max(worst, abs(got - want))
            cases += 1
    us = (time.time() - t0) * 1e6
    record("theorem3_diameter", us / cases, worst)


def bench_theorem4(quick: bool) -> None:
    from repro.core import algorithms as alg, gossip, lower_bound as lb
    from repro.core import topology as topo
    n, beta, T = 16, 1 - 1 / 16, 64
    inst = lb.make_instance2(L=1.0, Delta=10.0, n=n, beta=beta, T=T)
    I = inst.set1 + inst.set2
    graphs = topo.sun_shaped_schedule(n, beta, avoid=I)
    dist = topo.effective_distance(graphs, inst.set1, inst.set2,
                                   period=graphs.period)
    wsched = gossip.theorem3_weight_schedule(n, beta, avoid=I)

    def grad_fn(xs, key):
        return inst.grad_stacked(xs)

    algo = alg.dsgt(gamma=0.3)
    state = algo.init(jnp.zeros((n, inst.d)))
    state = alg.warm_start(algo, state, grad_fn, jax.random.key(0))
    step = jax.jit(algo.step, static_argnums=1)
    t0 = time.time()
    worst_ratio, t = 0.0, 0
    for k in range(T // 2):
        Ws = jnp.asarray(wsched.stacked(t, 2))
        state = step(state, grad_fn, Ws, jax.random.key(k))
        t += 2
        cap = t // dist + 1
        mp = max(int(lb.prog(state.x[i])) for i in range(n))
        worst_ratio = max(worst_ratio, mp / cap)
    us = (time.time() - t0) * 1e6
    record("theorem4_progress", us / (T // 2), round(worst_ratio, 3))


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def bench_kernels(quick: bool) -> None:
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.gossip_matmul import gossip_mix
    from repro.kernels.linear_recurrence import linear_recurrence
    from repro.core import gossip as G

    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=True))
    us, out = _timed(f, q, k, v)
    err = float(jnp.abs(out - ref.attention_ref(q, k, v)).max())
    record("kernel_flash_attention", us, f"{err:.2e}")

    a = jax.nn.sigmoid(jax.random.normal(ks[0], (1, 256, 256)))
    b = jax.random.normal(ks[1], (1, 256, 256))
    f = jax.jit(lambda a, b: linear_recurrence(a, b, interpret=True))
    us, out = _timed(f, a, b)
    err = float(jnp.abs(out[0] - ref.linear_recurrence_ref(a, b)[0]).max())
    record("kernel_linear_recurrence", us, f"{err:.2e}")

    from repro.kernels.decode_attention import decode_attention
    q1 = jax.random.normal(ks[0], (2, 1, 2, 4, 64))
    kc = jax.random.normal(ks[1], (2, 512, 2, 64))
    vc = jax.random.normal(ks[2], (2, 512, 2, 64))
    kpos = jnp.arange(512, dtype=jnp.int32)
    f = jax.jit(lambda q, k, v: decode_attention(q, k, v, kpos,
                                                 jnp.int32(511),
                                                 interpret=True))
    us, out = _timed(f, q1, kc, vc)
    err = float(jnp.abs(out - ref.decode_attention_ref(
        q1, kc, vc, kpos, jnp.int32(511))).max())
    record("kernel_decode_attention", us, f"{err:.2e}")

    sched = G.theorem3_weight_schedule(16, 0.9)
    ws = jnp.asarray(sched.stacked(0, 4), jnp.float32)
    x = jax.random.normal(ks[2], (16, 4096))
    f = jax.jit(lambda w, x: gossip_mix(w, x, interpret=True))
    us, out = _timed(f, ws, x)
    err = float(jnp.abs(out - ref.gossip_mix_ref(ws, x)).max())
    record("kernel_gossip_matmul", us, f"{err:.2e}")


# ---------------------------------------------------------------------------
# Compressed gossip: fused kernel vs unfused, convergence vs bandwidth
# ---------------------------------------------------------------------------

def bench_compression(quick: bool) -> None:
    """The compression-axis headline (ISSUE 7).  Rows:

    ``compression_fused_kernel`` — the fused Pallas
        ``quantized_gossip_mix`` (quantize -> mix -> dequantize -> residual
        for all R rounds in one pass) vs the unfused
        quantize-then-``gossip_mix`` path (R separate kernel launches with
        a full state round-trip between them).  derived = unfused us,
        speedup (> 1 = fused wins), and max |fused - unfused| (~0: both
        paths share the kernels/ref.py quantization math).
    ``compression_{none,sign,int8}`` — an ``exp.sweep`` over
        ``compression.scheme`` on the federated non-iid MC-DSGT scenario
        (error feedback on): final train loss vs the uncompressed run,
        nominal bytes/round from the manifest accounting, and measured
        cumulative wire bytes from the telemetry recorder.  The headline
        contract: sign stays within 10% of the uncompressed final loss at
        <= 1/8 the bytes/round.
    Writes experiments/bench/BENCH_compression.json (mirrored to the repo
    root under --json — the committed perf trajectory)."""
    import tempfile

    from repro import exp
    from repro.core import compress, gossip
    from repro.kernels import ops, ref

    w = BenchWriter()

    # fused vs unfused kernel wall time
    n, R = 16, 4
    D = 65536 if quick else 1 << 18
    sched = gossip.theorem3_weight_schedule(n, 0.9)
    ws = jnp.asarray(sched.stacked(0, R), jnp.float32)
    x = jax.random.normal(jax.random.key(0), (n, D))
    res = jnp.zeros_like(x)

    @jax.jit
    def fused(ws, x, res):
        return ops.quantized_gossip_mix(ws, x, res, scheme="sign",
                                        use_pallas=True)

    @jax.jit
    def unfused(ws, x, res):
        for r in range(R):
            deq, err = ref.quantize_dequantize_ref(x + res, scheme="sign")
            res = err
            x = ops.gossip_mix(ws[r:r + 1], deq, use_pallas=True)
        return x, res

    us_f, out_f = _timed(fused, ws, x, res)
    us_u, out_u = _timed(unfused, ws, x, res)
    err = max(float(jnp.abs(a - b).max()) for a, b in zip(out_f, out_u))
    w.row("compression_fused_kernel", us_f,
          f"unfused_us={us_u:.1f}|speedup={us_u / max(us_f, 1e-9):.2f}x"
          f"|rounds={R}|D={D}|err={err:.1e}")

    # convergence vs bandwidth per scheme (the perf/quality headline)
    steps = 6 if quick else 12
    base = exp.from_dict({
        "algorithm": {"name": "mc_dsgt", "R": 2, "gamma": 0.1},
        "data": {"batch": 2, "seq": 32, "hetero_alpha": 0.3},
        "topology": {"kind": "federated", "local_steps": 4},
        "run": {"steps": steps, "nodes": 4, "log_every": steps}})
    finals = {}
    with tempfile.TemporaryDirectory() as td:
        for spec in exp.sweep(base, {"compression.scheme":
                                     list(exp.COMPRESSIONS)}):
            scheme = spec.compression.scheme
            spec = exp.with_field(spec, "run.telemetry",
                                  os.path.join(td, f"{scheme}.json"))
            t0 = time.time()
            r = exp.run(spec, quiet=True)
            us = (time.time() - t0) * 1e6 / steps
            loss = float(r.history[-1]["loss"])
            finals[scheme] = loss
            bpr = compress.payload_bytes(r.built.state_dim, scheme,
                                         spec.compression.group)
            bpr0 = compress.payload_bytes(r.built.state_dim, "none")
            w.row(f"compression_{scheme}", us,
                  f"final_loss={loss:.4f}"
                  f"|vs_none={loss / finals['none']:.4f}"
                  f"|bytes_per_round={bpr}"
                  f"|bytes_vs_none={bpr / bpr0:.4f}"
                  f"|wire_bytes_total={r.telemetry.bytes_total}",
                  spec=spec, throughput=round(1e6 / us, 2))
    w.dump("experiments/bench/BENCH_compression.json")


# ---------------------------------------------------------------------------
# Gossip planning: dense einsum vs structured lowering, per topology
# ---------------------------------------------------------------------------

def bench_gossip_plan(quick: bool) -> None:
    """Times one full schedule period of multi-consensus on an (n, D) state:
    the dense einsum stack vs the structured GossipPlan lowering the auto
    dispatcher picks, one row per topology of an ``exp.sweep`` grid.
    derived = auto path us, speedup, the plan's round kinds, and
    max |dense - auto| (must be ~0).  Writes BENCH_gossip_plan.json."""
    from repro import exp
    from repro.core import algorithms as alg
    from repro.dist.collectives import stage_plan

    n = 16
    D = 65536 if quick else 1 << 20
    x = jax.random.normal(jax.random.key(0), (n, D))
    base = exp.ExperimentSpec(topology=exp.TopologySpec(beta=0.75),
                              run=exp.RunSpec(nodes=n))
    w = BenchWriter()
    for spec in exp.sweep(base, {"topology.kind": [
            "sun", "one-peer-exp", "federated", "complete",
            "random-matching", "erdos-renyi"]}):
        sched = exp.build_topology(spec.topology, n, seed=spec.run.seed)
        P = sched.period
        plan = sched.plan(0, P)
        Ws = jnp.asarray(sched.stacked(0, P))
        tensors = stage_plan(plan)
        mixer = alg.make_plan_mixer(plan, mode="static")
        dense_f = jax.jit(lambda Ws, x: alg.multi_consensus(Ws, x))
        auto_f = jax.jit(lambda T, x: mixer(T, 0, P, x))
        us_d, out_d = _timed(dense_f, Ws, x)
        us_a, out_a = _timed(auto_f, tensors, x)
        err = float(jnp.abs(out_d - out_a).max())
        kinds = ",".join(sorted(set(plan.kinds)))
        w.row(f"gossip_plan_{spec.topology.kind}", us_d,
              f"auto_us={us_a:.1f}|speedup={us_d / max(us_a, 1e-9):.2f}x"
              f"|kinds={kinds}|err={err:.1e}", spec=spec)
    w.dump("experiments/bench/BENCH_gossip_plan.json")


# ---------------------------------------------------------------------------
# repro.sim: mobility resampling, fault realization, plan restaging
# ---------------------------------------------------------------------------

def bench_sim(quick: bool) -> None:
    """Throughput of the wireless-simulation data path, per stage: mobility
    schedule resampling (unit-disk adjacency rounds), channel+repair
    realization (ideal W -> masked -> repaired), and plan restaging
    (WeightSchedule.plan + stage_plan of the realized window).  Every stage
    is keyed by the scenario spec it realizes.  derived = rounds/s (and the
    realized plan's kind counts for the restage row).  Also writes
    experiments/bench/BENCH_sim.json — a CI artifact."""
    from repro import exp
    from repro.dist.collectives import stage_plan
    from repro.sim import (random_geometric_schedule,
                           random_waypoint_schedule,
                           realize_weight_schedule)

    n = 16
    rounds = 64 if quick else 256
    base = exp.ExperimentSpec(run=exp.RunSpec(nodes=n))
    w = BenchWriter()

    # time the RAW topology resampling (per-round unit-disk adjacency
    # draws) — exp.build_topology would pre-materialize the whole window
    # outside the timed region and we'd be benchmarking tuple indexing
    _mobility = {"geometric-mobility": random_geometric_schedule,
                 "waypoint-mobility": random_waypoint_schedule}
    for spec in exp.sweep(base, {"topology.kind": list(_mobility)}):
        sched = _mobility[spec.topology.kind](
            n, spec.topology.radius, seed=spec.run.seed)
        t0 = time.time()
        for t in range(rounds):
            sched(t)
        us = (time.time() - t0) * 1e6 / rounds
        tag = spec.topology.kind.split("-")[0]
        w.row(f"sim_resample_{tag}", us, f"rounds_per_s={1e6 / us:.0f}",
              spec=spec)

    wspec = exp.with_overrides(base, {
        "topology.kind": "waypoint-mobility",
        "channel.link_drop": 0.2, "channel.burst_loss": 0.1})
    ideal = exp.build_topology(wspec.topology, n, horizon=rounds,
                               seed=wspec.run.seed)
    models = exp.build_channel_models(wspec.channel, wspec.run.seed)
    t0 = time.time()
    realized = realize_weight_schedule(ideal, models, rounds=rounds)
    us = (time.time() - t0) * 1e6 / rounds
    w.row("sim_realize_channel_repair", us, f"rounds_per_s={1e6 / us:.0f}",
          spec=wspec)

    t0 = time.time()
    plan = realized.plan(0, rounds)
    tensors = stage_plan(plan)
    jax.block_until_ready(tensors)
    us = (time.time() - t0) * 1e6 / rounds
    kinds = "+".join(f"{plan.kinds.count(k)}x{k}"
                     for k in dict.fromkeys(plan.kinds))
    epr = []
    for rd in plan.rounds:
        off = np.abs(rd.W) > 1e-12
        np.fill_diagonal(off, False)
        epr.append(int(off.sum()))
    w.row("sim_plan_restage", us,
          f"rounds_per_s={1e6 / us:.0f}|kinds={kinds}"
          f"|edges_per_round={np.mean(epr):.0f}", spec=wspec)

    w.dump("experiments/bench/BENCH_sim.json")


# ---------------------------------------------------------------------------
# Sparse scenario engine: staging vs n, dense comparison, segment-sum mixer
# ---------------------------------------------------------------------------

def bench_sparse(quick: bool) -> None:
    """Throughput of the sparse scenario engine per stage and node count:
    realize (sampled cohort + unit-disk + Metropolis edges), repair
    (per-edge channel masks), and restage (SparseGossipPlan + padded
    tensors) at n in {64, 1k, 10k, 100k} with a fixed per-round cohort —
    the headline claim is near-flat us/round as n grows, because every
    stage is O(edges) = O(k^2), never O(n^2).  The dense pipeline runs the
    SAME sampled rounds at the n where (n, n) materialization is feasible,
    as the baseline it escapes.  A final pair of rows prices one edge-list
    gossip round through the jnp segment-sum reference vs the fused Pallas
    kernel (derived = max |fused - unfused|).  Also writes
    experiments/bench/BENCH_sparse.json — a CI artifact."""
    from repro import exp, sparse
    from repro.core import gossip, topology as topo
    from repro.kernels import ops as kops
    from repro.sim import channel as sim_channel

    k = 64
    rounds = 8 if quick else 32
    sizes = (64, 1_000, 10_000, 100_000)
    dense_sizes = (64, 1_000)
    w = BenchWriter()

    for n in sizes:
        kk = min(k, n)
        spec = exp.ExperimentSpec(
            model=exp.ModelRef(kind="logreg"),
            topology=exp.TopologySpec(kind="random-sampled", sample_k=kk),
            channel=exp.ChannelSpec(link_drop=0.2),
            run=exp.RunSpec(nodes=n, gossip_impl="auto"))
        models = exp.build_channel_models(spec.channel, spec.run.seed)

        t0 = time.time()
        ideal = sparse.sampled_weight_schedule(n, kk, horizon=rounds)
        us = (time.time() - t0) * 1e6 / rounds
        epr = float(ideal.edges_per_round.mean())
        w.row(f"sparse_realize_n{n}", us,
              f"rounds_per_s={1e6 / us:.0f}|edges_per_round={epr:.0f}",
              spec=spec)

        t0 = time.time()
        real = sparse.realize_sparse_schedule(ideal, models)
        us = (time.time() - t0) * 1e6 / rounds
        w.row(f"sparse_repair_n{n}", us,
              f"rounds_per_s={1e6 / us:.0f}|edges_per_round="
              f"{real.edges_per_round.mean():.0f}", spec=spec)

        t0 = time.time()
        plan = real.plan(validate=False)
        tensors = {key: jnp.asarray(v) for key, v in plan.tensors().items()}
        jax.block_until_ready(tensors)
        us = (time.time() - t0) * 1e6 / rounds
        kinds = "+".join(f"{plan.kinds.count(kd)}x{kd}"
                         for kd in dict.fromkeys(plan.kinds))
        w.row(f"sparse_restage_n{n}", us,
              f"rounds_per_s={1e6 / us:.0f}|kinds={kinds}", spec=spec)

        if n in dense_sizes:
            # the dense pipeline on the SAME realized rounds: materialize
            # (n, n) matrices, classify, and lower through the dense planner
            t0 = time.time()
            mats = [real(t) for t in range(rounds)]
            ws = gossip.WeightSchedule(
                tuple(mats),
                tuple(topo.classify_adjacency(np.abs(M) > 1e-12)
                      for M in mats))
            dplan = ws.plan(0, rounds, sparse=False)
            jax.block_until_ready(
                {key: jnp.asarray(v) for key, v in dplan.tensors().items()})
            us = (time.time() - t0) * 1e6 / rounds
            w.row(f"sparse_dense_path_n{n}", us,
                  f"rounds_per_s={1e6 / us:.0f}", spec=spec)

    # fused vs unfused segment-sum mix of one realized round (n=1k cohort)
    rd = sparse.SampledMobilitySchedule(1_000, min(256, k * 4)).round(0)
    plan1 = sparse.SparseGossipPlan.from_rounds([rd])
    tt = plan1.tensors()
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((1_000, 256)), jnp.float32)
    args = tuple(jnp.asarray(tt[key][0])
                 for key in ("esrc", "edst", "ew", "seg", "slots"))
    us_ref, out_ref = _timed(
        lambda: kops.sparse_gossip_mix(x, *args, use_pallas=False))
    us_pal, out_pal = _timed(
        lambda: kops.sparse_gossip_mix(x, *args, use_pallas=True))
    err = float(jnp.max(jnp.abs(out_ref - out_pal)))
    w.row("sparse_mix_segment_unfused", us_ref,
          f"edges={rd.edges}|dim=256")
    w.row("sparse_mix_segment_fused", us_pal,
          f"edges={rd.edges}|dim=256|max_err={err:.2e}")
    assert err < 1e-4, f"fused segment mix diverged: {err}"

    w.dump("experiments/bench/BENCH_sparse.json")


# ---------------------------------------------------------------------------
# Engine step throughput (one row per update rule)
# ---------------------------------------------------------------------------

def bench_engine_step(quick: bool) -> None:
    """Throughput of the engine-built distributed train step for EVERY
    update rule the single-source engine defines — an ``exp.sweep`` over
    ``algorithm.name`` on the reduced qwen config with dense gossip, each
    row realized via ``exp.build``.  derived = steps/s and the rule's
    gossip rounds per step.  Also writes
    experiments/bench/BENCH_engine.json — the BENCH trajectory artifact CI
    uploads."""
    from repro import exp
    from repro.dist import steps as dsteps

    n = 4
    base = exp.ExperimentSpec(
        data=exp.DataSpec(batch=1, seq=16, active_vocab=16),
        topology=exp.TopologySpec(kind="sun", beta=0.5),
        run=exp.RunSpec(nodes=n))
    w = BenchWriter()
    for spec in exp.sweep(base, {"algorithm.name": list(exp.ALGORITHMS)}):
        spec = exp.with_field(spec, "algorithm.R",
                              2 if spec.algorithm.name == "mc_dsgt" else 1)
        b = exp.build(spec)
        init_s, warm, step = dsteps.make_train_step(
            b.model, b.cfg, algo=spec.algorithm.name,
            gamma=spec.algorithm.gamma, R=b.rule.R)
        state = warm(init_s(jax.random.key(spec.run.seed), n, jnp.float32),
                     b.stream.batch_at(0))
        W = jnp.asarray(b.schedule.stacked(0, b.wps))
        us, _ = _timed(jax.jit(step), state, b.stream.batch_at(1), W)
        w.row(f"engine_step_{spec.algorithm.name}", us,
              f"steps_per_s={1e6 / max(us, 1e-9):.1f}|wps={b.wps}",
              spec=spec)
    w.dump("experiments/bench/BENCH_engine.json")


# ---------------------------------------------------------------------------
# Async overlapped gossip (stale-window delay)
# ---------------------------------------------------------------------------

def bench_async(quick: bool) -> None:
    """The overlapped-gossip runtime (stale-window delay).  Two row groups:

    ``async_step_*`` — steady-state step time of the distributed train
        step on the BENCH_engine LM scenario (reduced qwen, 4 nodes, sun
        schedule): dsgd and mc_dsgt synchronous, then mc_dsgt with
        ``delay=1`` (the double-buffered overlap path).  Each delayed row's
        derived carries the :func:`repro.obs.overlap_report` verdict —
        the jaxpr-level proof that no obs_mix op consumes an obs_grad
        output — and ``async_overlap_ratio`` reports the headline
        mc_dsgt(delay=1)/dsgd ratio (contract: <= 1.3 with overlap on;
        note XLA:CPU schedules conservatively, so the wall-clock win is
        a TPU property — the ratio row still tracks the trend and the
        overlap_ok flag is backend-independent).
    ``async_converge_delay{0,1,2}`` — the Figure-2 scenario (non-convex
        logistic regression, Dirichlet-heterogeneous data, random sun
        graphs, mc_dsgt R=2): final loss under each staleness window.
        derived = final loss and ``delta_frac``, the |final - sync final|
        as a fraction of the synchronous run's total descent (contract:
        <= 2%).  Fixed length by design — staleness x step-size trades
        off like momentum, so the comparison is at a matched budget.
    Writes experiments/bench/BENCH_async.json."""
    from repro import exp
    from repro.dist import steps as dsteps
    from repro.obs import overlap_report

    n = 4
    lm = exp.ExperimentSpec(
        data=exp.DataSpec(batch=1, seq=16, active_vocab=16),
        topology=exp.TopologySpec(kind="sun", beta=0.5),
        run=exp.RunSpec(nodes=n))
    w = BenchWriter()
    times, reps = {}, {}
    for algo, delay in [("dsgd", 0), ("mc_dsgt", 0), ("mc_dsgt", 1)]:
        spec = exp.with_overrides(lm, {
            "algorithm.name": algo, "algorithm.delay": delay,
            "algorithm.R": 2 if algo == "mc_dsgt" else 1})
        b = exp.build(spec)
        init_s, warm, step = dsteps.make_train_step(
            b.model, b.cfg, algo=algo, gamma=spec.algorithm.gamma,
            R=b.rule.R, delay=delay)
        state = warm(init_s(jax.random.key(spec.run.seed), n, jnp.float32),
                     b.stream.batch_at(0))
        W = jnp.asarray(b.schedule.stacked(0, b.wps))
        batch = b.stream.batch_at(1)
        us, _ = _timed(jax.jit(step), state, batch, W)
        rep = overlap_report(step, state, batch, W)  # un-jitted: real eqns
        times[(algo, delay)] = us
        reps[(algo, delay)] = rep
        w.row(f"async_step_{algo}_delay{delay}", us,
              f"steps_per_s={1e6 / max(us, 1e-9):.1f}"
              f"|overlap_ok={rep['overlapped']}", spec=spec)
    ratio = times[("mc_dsgt", 1)] / max(times[("dsgd", 0)], 1e-9)
    w.row("async_overlap_ratio", times[("mc_dsgt", 1)],
          f"ratio_vs_dsgd={ratio:.2f}|target=1.3"
          f"|overlap_ok={reps[('mc_dsgt', 1)]['overlapped']}")

    steps_c, gamma = (40, 0.05) if quick else (60, 0.05)
    base_spec = exp.ExperimentSpec(
        model=exp.ModelRef(kind="logreg", d=16, m=256),
        data=exp.DataSpec(batch=8, hetero_alpha=0.5),
        algorithm=exp.AlgorithmSpec(name="mc_dsgt", gamma=gamma, R=2),
        topology=exp.TopologySpec(kind="random-sun"),
        run=exp.RunSpec(steps=steps_c, nodes=8))
    finals = {}
    for delay in (0, 1, 2):
        spec = exp.with_field(base_spec, "algorithm.delay", delay)
        t0 = time.time()
        hist = exp.run(spec, quiet=True).history
        us = (time.time() - t0) * 1e6 / steps_c
        init, final = float(hist[0][1]), float(hist[-1][1])
        finals[delay] = (init, final)
        descent = max(finals[0][0] - finals[0][1], 1e-12)
        delta = abs(final - finals[0][1]) / descent
        w.row(f"async_converge_delay{delay}", us,
              f"final={final:.5f}|delta_frac={delta:.4f}|target=0.02",
              spec=spec)
    w.dump("experiments/bench/BENCH_async.json")


# ---------------------------------------------------------------------------
# Observability overhead (repro.obs)
# ---------------------------------------------------------------------------

def bench_obs(quick: bool) -> None:
    """Cost of measuring a run.  Two rows:

    ``obs_run_overhead`` — steady-state per-step wall time of the shared
        driver loop on the quickstart workload (logreg d=64 m=256, 16
        nodes, mc_dsgt R=4 over the theorem-3 sun schedule) at three
        observability levels: ``bare`` (no recorder; the loop's
        always-on ``data``/``dispatch`` spans still run, since no run is
        without them), ``injit`` (the in-jit metric scalars only), and
        ``full`` (ObsRecorder + its span tracer + gap tracker + JSONL sink
        at every=10).  The loop is pre-compiled
        and timed over interleaved repetitions (median), so compile and
        dataset costs never enter — unlike wall-clocking ``exp.run``,
        which re-jits per call and drowns a us-scale delta in ~1s of
        compile noise.  derived = in-jit and full overhead fractions.
        The PR's contract (< 5% at every=10) targets the hot-path cost:
        with >= 2 cores the background flusher overlaps the drain work
        (host transfer + json + gap update, ~15 us/step amortized); on a
        single-core container everything serializes onto one core and
        the full fraction reads higher — ``ncores`` is recorded so the
        number can be judged in context.
    ``obs_telemetry_cache`` — TelemetryRecorder's per-record window
        materialization (float64 stack + adjacency + kind counts of the
        trailing rounds) with the per-round cache vs the uncached
        per-call re-stack, sliding over a realized wireless schedule.
        derived = speedup (O(window) -> O(new rounds) per call) and the
        full ``record()`` time for context.
    Writes experiments/bench/BENCH_obs.json."""
    import statistics
    import tempfile

    from repro import exp
    from repro.core import algorithms as alg
    from repro.core import driver, engine
    from repro.data.synthetic import logreg_dataset, logreg_loss_and_grad
    from repro.obs import EventLog, GapTracker, ObsRecorder, Tracer
    from repro.sim import realize_weight_schedule
    from repro.sim.telemetry import TelemetryRecorder

    # the quickstart cell: mc_dsgt R=4 gamma=0.4 on a beta=.9375 sun
    base = exp.from_dict({
        "model": {"kind": "logreg", "d": 64, "m": 256, "rho": 0.1},
        "data": {"batch": 16},
        "algorithm": {"name": "mc_dsgt", "R": 4, "gamma": 0.4},
        "topology": {"kind": "sun", "beta": 0.9375},
        "run": {"nodes": 16}})
    n, d = 16, 64
    H, y = logreg_dataset(n, 256, d, seed=0)
    _, _, stoch, _, _ = logreg_loss_and_grad(rho=0.1)
    grad_fn = lambda xs, key: stoch(xs, H, y, key, 16)  # noqa: E731
    sched = exp.build_topology(base.topology, n, seed=0)
    algo = alg.mc_dsgt(0.4, R=4)
    rule = engine.make_rule("mc_dsgt", gamma=0.4, R=4)
    names = engine.default_obs(rule)
    wps = algo.weights_per_step
    N, reps = (300, 3) if quick else (1000, 5)
    staged = driver.stage(sched, wps=wps, total=N * wps)

    def _step(obs):
        def core(state, sub, weights, t):
            out = algo.step(state, grad_fn, weights, sub, obs=obs)
            return (out[0], {"obs": out[1]}) if obs else (out, None)
        return driver.bind_step(staged, core)

    steps = {"bare": _step(()), "obs": _step(names)}
    state0 = algo.warm(algo.init(jnp.zeros((n, d))), grad_fn,
                       jax.random.key(1))
    key = [jax.random.key(0)]

    def extra_fn(k):
        key[0], sub = jax.random.split(key[0])
        return sub

    def _loop(step, record=None, tracer=None, steps_n=N):
        t0 = time.time()
        driver.run_loop(step, state0, steps=steps_n, wps=wps,
                        period=staged.period, extra_fn=extra_fn,
                        record=record, tracer=tracer)
        return (time.time() - t0) * 1e6 / steps_n

    w = BenchWriter()
    with tempfile.TemporaryDirectory() as td:

        def run_level(level, steps_n=N):
            if level != "full":
                return _loop(steps["bare" if level == "bare" else "obs"],
                             steps_n=steps_n)
            tracer = Tracer()
            rec = ObsRecorder(
                EventLog(os.path.join(td, f"b{time.time_ns()}.jsonl")),
                every=10, tracer=tracer,
                gap=GapTracker(cell="bench", n=n, beta=0.5))
            us = _loop(steps["obs"], record=rec.record, tracer=tracer,
                       steps_n=steps_n)
            rec.close()
            return us

        levels = ("bare", "injit", "full")
        for lv in levels:  # compile + warm outside the clock
            run_level(lv, steps_n=30)
        res = {lv: [] for lv in levels}
        for _ in range(reps):  # interleave: reps share drift/noise
            for lv in levels:
                res[lv].append(run_level(lv))
    bare, injit, full = (statistics.median(res[lv]) for lv in levels)
    w.row("obs_run_overhead", full,
          f"bare_us={bare:.1f}|injit_us={injit:.1f}"
          f"|injit_overhead={100 * (injit - bare) / bare:.1f}%"
          f"|full_overhead={100 * (full - bare) / bare:.1f}%"
          f"|every=10|ncores={os.cpu_count()}",
          spec=base)

    wspec = exp.from_dict({
        "topology": {"kind": "waypoint-mobility", "radius": 0.45},
        "channel": {"link_drop": 0.2, "burst_loss": 0.1},
        "run": {"nodes": 16}})
    calls = 40 if quick else 120
    window, wps = 32, 2
    horizon = window + wps * (calls + 4) + 8
    ideal = exp.build_topology(wspec.topology, 16, horizon=horizon, seed=0)
    models = exp.build_channel_models(wspec.channel, 0)
    realized = realize_weight_schedule(ideal, models, rounds=horizon)

    class _S:
        x = jnp.ones((16, 8))

    mat_times, rec_times = {}, {}
    for cache in (True, False):
        telem = TelemetryRecorder(realized, wps=wps, window=window,
                                  cache=cache)
        telem._window_rounds(0, window)  # warm numpy/jax paths
        t0 = time.time()
        for k in range(calls):  # the sliding-window materialization alone
            lo = wps * (k + 1)
            telem._window_rounds(lo, lo + window)
        mat_times[cache] = (time.time() - t0) * 1e6 / calls
        telem2 = TelemetryRecorder(realized, wps=wps, window=window,
                                   cache=cache)
        for k in range(4):  # warm outside the clock
            telem2.record(k, window + (k + 1) * wps, _S(), None, 0.0)
        t0 = time.time()
        for k in range(4, 4 + calls):
            telem2.record(k, window + (k + 1) * wps, _S(), None, 0.0)
        rec_times[cache] = (time.time() - t0) * 1e6 / calls
    w.row("obs_telemetry_cache", mat_times[True],
          f"uncached_us={mat_times[False]:.1f}"
          f"|speedup={mat_times[False] / max(mat_times[True], 1e-9):.2f}x"
          f"|record_us={rec_times[True]:.0f}|window={window}", spec=wspec)
    w.dump("experiments/bench/BENCH_obs.json")


# ---------------------------------------------------------------------------
# Personalized fleet serving (continuous batching)
# ---------------------------------------------------------------------------

def bench_serve(quick: bool) -> None:
    """Continuous-batching serve throughput (ISSUE 10): one row per
    decode-slot count, serving synthetic user-affinity traffic against a
    stacked reduced-qwen fleet through :func:`repro.serve.serve_fleet`.
    derived = prefill/decode token throughput and the p50/p95 request
    latency (larger slot tables amortize the vmapped decode but queue
    admissions, so latency and throughput trade off against ``batch``).
    Row throughput = completed requests/s — the regression-gate metric.
    Writes experiments/bench/BENCH_serve.json."""
    from repro import exp
    from repro.serve import serve_fleet

    fleet_n = 4
    requests = 16 if quick else 64
    base = exp.ExperimentSpec(
        model=exp.ModelRef(kind="arch", arch="qwen1.5-0.5b",
                           preset="reduced"),
        run=exp.RunSpec(nodes=fleet_n),
        serve=exp.ServeSpec(requests=requests, prompt_len=16, max_new=8,
                            dtype="f32"))
    b = exp.build(base)
    keys = jax.random.split(jax.random.key(0), fleet_n)
    fleet = jax.vmap(lambda k: b.model.init(k, jnp.float32))(keys)
    w = BenchWriter()
    for batch in ((2, 8) if quick else (2, 8, 16)):
        spec = exp.with_field(base, "serve.batch", batch)
        serve_fleet(b.model, fleet, spec.serve)  # warmup/compile pass
        t0 = time.time()
        res = serve_fleet(b.model, fleet, spec.serve)
        us = (time.time() - t0) * 1e6 / requests
        tp = res.throughput
        w.row(f"serve_batch{batch}", us,
              f"prefill_tok_s={tp['prefill_tok_s']}"
              f"|decode_tok_s={tp['decode_tok_s']}"
              f"|p50_ms={tp['latency_p50_ms']}"
              f"|p95_ms={tp['latency_p95_ms']}"
              f"|requests={tp['requests']}|fleet={fleet_n}",
              spec=spec, throughput=tp["requests_per_s"])
    w.dump("experiments/bench/BENCH_serve.json")


# ---------------------------------------------------------------------------
# Roofline summary (from dry-run artifacts)
# ---------------------------------------------------------------------------

def bench_roofline(quick: bool) -> None:
    paths = sorted(glob.glob("experiments/dryrun/*.json"))
    if not paths:
        record("roofline_summary", 0.0, "no-dryrun-artifacts")
        return
    from repro.launch.roofline import analyse
    t0 = time.time()
    dom = {"compute": 0, "memory": 0, "collective": 0}
    for p in paths:
        rec = json.load(open(p))
        dom[analyse(rec)["dominant"]] += 1
    us = (time.time() - t0) * 1e6
    record("roofline_summary", us / len(paths),
           f"compute:{dom['compute']}|memory:{dom['memory']}"
           f"|collective:{dom['collective']}")


BENCHES = [
    ("theorem3", bench_theorem3),
    ("compression", bench_compression),
    ("gossip_plan", bench_gossip_plan),
    ("sim", bench_sim),
    ("sparse", bench_sparse),
    ("engine_step", bench_engine_step),
    ("async", bench_async),
    ("obs", bench_obs),
    ("serve", bench_serve),
    ("kernels", bench_kernels),
    ("theorem4", bench_theorem4),
    ("table1_rate_T", bench_table1_rate_T),
    ("table1_speedup_n", bench_table1_speedup_n),
    ("r_ablation", bench_r_ablation),
    ("figure2", bench_figure2),
    ("roofline", bench_roofline),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only benchmarks whose name contains SUBSTR "
                         "(e.g. --only engine_step for the CI artifact)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results to a BENCH json (default "
                         "experiments/bench/BENCH.json under --quick)")
    args, _ = ap.parse_known_args()
    quick = args.quick
    json_path = args.json or (quick and "experiments/bench/BENCH.json" or None)
    if args.json:  # --json opts into the root-canonical BENCH mirror
        global MIRROR_TO_ROOT
        MIRROR_TO_ROOT = True

    print("name,us_per_call,derived")
    for name, fn in BENCHES:
        if args.only is None or args.only in name:
            fn(quick)
    if json_path:
        if os.path.dirname(json_path):
            os.makedirs(os.path.dirname(json_path), exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(ALL_ROWS, f, indent=1)
        print(f"wrote {json_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
