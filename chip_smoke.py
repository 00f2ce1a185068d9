#!/usr/bin/env python3
"""Chip smoke test: the decentralized training path once on a TPU.

Default (one chip), in one process:

1. **train** — MC-DSGT training of qwen1.5-0.5b at its published widths
   (24 layers, d_model 1024, vocab 151936; random weights from the seed)
   through the front door, ``exp.run(spec)``: 2 nodes stacked on the chip,
   one-peer exponential topology, ``gossip_impl="auto"``, batch 1.  Checks
   finite losses, the state's shapes and the gradient-tracking invariant
   mean_i h_i == mean_i g_prev_i.
2. **gossip** — the fused Pallas gossip kernel (``ops.gossip_mix``) at the
   width of one MLP matrix, compiled (its HLO must hold a
   ``tpu_custom_call``), against ``kernels.ref.gossip_mix_ref``.

``--four-chips`` runs only the distributed path of ``repro.dist``: one
full-width MC-DSGT step with one node per chip on a 4-device mesh, the
node axis sharded by ``dist.sharding.param_specs``, matching rounds
lowered to ``collective-permute`` under ``gossip_impl="auto"`` and
compared with the same step under ``gossip_impl="dense"``.

Usage::

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips

Earlier lines of stdout are one JSON object per phase; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and prints no result.  Each phase
is a function of the preset, so the tests run them on the CPU at the
reduced size.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

# outside a checkout these fail before JAX touches a device
from repro import configs, exp  # noqa: E402
from repro.dist import sharding as shd, steps as dsteps  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.obs.trace import compile_counts  # noqa: E402

ARCH = "qwen1.5-0.5b"
# the largest step the compiler fits in one v5e's HBM at full width with
# two f32 node copies (x, h, g_prev) and the state donated
TRAIN = dict(nodes=2, steps=5, R=1, seq=128)

def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _spec(preset: str, *, nodes: int, steps: int, R: int, seq: int):
    return exp.ExperimentSpec(
        model=exp.ModelRef(kind="arch", arch=ARCH, preset=preset),
        data=exp.DataSpec(batch=1, seq=seq),
        algorithm=exp.AlgorithmSpec(name="mc_dsgt", R=R),
        topology=exp.TopologySpec(kind="one-peer-exp"),
        run=exp.RunSpec(steps=steps, nodes=nodes, gossip_impl="auto"))


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train_phase(preset: str, *, nodes: int, steps: int, R: int,
                seq: int) -> dict:
    """``exp.run`` of the main path; raises if any check fails."""
    spec = _spec(preset, nodes=nodes, steps=steps, R=R, seq=seq)
    c0 = compile_counts()
    t0 = time.perf_counter()
    res = exp.run(spec, quiet=True)
    state = jax.block_until_ready(res.state)
    wall = time.perf_counter() - t0

    hist = res.history
    _check(len(hist) == steps, f"{len(hist)} history rows for {steps} steps")
    losses = [h["loss"] for h in hist]
    _check(all(math.isfinite(v) for v in losses), f"losses {losses}")
    ready = [h["ready"] for h in hist]
    step_s = [b - a for a, b in zip(ready, ready[1:])]
    _check(int(state.step) == steps, f"step counter {int(state.step)}")

    # shapes: every leaf is `nodes` f32 copies of the configured model
    built = res.built
    want = jax.eval_shape(lambda k: built.model.init(k, jnp.float32),
                          jax.random.key(0))
    got = [(l.shape, l.dtype) for l in jax.tree.leaves(state.x)]
    exp_shapes = [((nodes,) + w.shape, w.dtype) for w in jax.tree.leaves(want)]
    _check(got == exp_shapes, f"state shapes {got} != {exp_shapes}")
    params = sum(math.prod(s) for s, _ in exp_shapes) // nodes

    # gradient tracking (Algorithm 1): doubly stochastic mixing keeps the
    # node-mean tracker equal to the node-mean oracle sample
    gap = scale = 0.0
    for h, g in zip(jax.tree.leaves(state.h), jax.tree.leaves(state.g_prev)):
        hm, gm = jnp.mean(h, axis=0), jnp.mean(g, axis=0)
        gap = max(gap, float(jnp.max(jnp.abs(hm - gm))))
        scale = max(scale, float(jnp.max(jnp.abs(gm))))
    _check(scale > 0 and gap <= 1e-4 * scale,
           f"tracker mean gap {gap} at scale {scale}")

    c1 = compile_counts()
    return {"phase": "train", "arch": built.cfg.name, "preset": preset,
            "params_per_node": params, "nodes": nodes, "R": R, "seq": seq,
            "batch": spec.data.batch, "steps": steps,
            "plan_kinds": sorted(set(built.plan.kinds)),
            "losses": losses, "first_step_s": ready[0] - t0,
            "step_s": step_s, "wall_s": wall,
            "compile_s": c1["compile_s"] - c0["compile_s"],
            "cache_hits": c1["cache_hits"] - c0["cache_hits"],
            "cache_misses": c1["cache_misses"] - c0["cache_misses"],
            "tracker_gap": gap, "tracker_scale": scale,
            "peak_bytes_in_use": _peak_bytes()}


def gossip_phase(preset: str, *, require_kernel: bool, n: int = 4,
                 rounds: int = 3, reps: int = 5) -> dict:
    """The fused gossip kernel at one MLP matrix's width vs the reference.
    ``require_kernel``: the compiled HLO must hold the Mosaic kernel (off
    only where the kernel runs in interpret mode, on the CPU)."""
    cfg = configs.get(ARCH)
    if preset == "reduced":
        cfg = cfg.reduced()
    D = cfg.d_model * cfg.d_ff
    sched = exp.build_topology(exp.TopologySpec(kind="ring"), n,
                               horizon=rounds, seed=0)
    ws = jnp.asarray(sched.stacked(0, rounds), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (n, D), jnp.float32)

    fn = jax.jit(lambda w, v: ops.gossip_mix(w, v, use_pallas=True))
    compiled = fn.lower(ws, x).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if require_kernel:
        _check(has_kernel, "gossip_mix compiled without its Pallas kernel")
    out = jax.block_until_ready(compiled(ws, x))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = compiled(ws, x)
    jax.block_until_ready(out)
    sec = (time.perf_counter() - t0) / reps

    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.gossip_mix_ref)(ws, x)
    err = float(jnp.max(jnp.abs(out - want)))
    scale = float(jnp.max(jnp.abs(want)))
    _check(out.shape == (n, D) and err <= 1e-5 * scale,
           f"gossip kernel {out.shape}: max error {err} at scale {scale}")
    return {"phase": "gossip", "n": n, "D": D, "rounds": rounds,
            "tpu_custom_call": has_kernel, "max_abs_err": err,
            "ref_scale": scale, "call_s": sec}


def four_chip_programs(preset: str, mesh, *, seq: int, R: int):
    """The jitted, sharded programs of the four-chip phase on ``mesh``
    (a 1-axis ``("data",)`` mesh of 4 devices): init, warm start, and one
    MC-DSGT step under ``gossip_impl`` ``auto`` (ppermute) and ``dense``.
    Separate from the run so a described topology can compile them."""
    nodes = mesh.devices.size
    spec = _spec(preset, nodes=nodes, steps=1, R=R, seq=seq)
    built = exp.build(spec)

    def make(impl):
        return dsteps.make_train_step(
            built.model, built.cfg, algo="mc_dsgt",
            gamma=spec.algorithm.gamma, R=built.rule.R, gossip_impl=impl,
            plan=built.plan if impl == "auto" else None,
            mesh=mesh if impl == "auto" else None, gossip_axis="data")

    init_state, warm, step_auto = make("auto")
    _, _, step_dense = make("dense")
    _check(step_auto.gossip_dispatch == "static",
           f"plan dispatch {step_auto.gossip_dispatch}")

    named = lambda tree: jax.tree.map(lambda p: NamedSharding(mesh, p), tree,
                                      is_leaf=lambda p: isinstance(p, P))
    shapes = jax.eval_shape(lambda k: init_state(k, nodes, jnp.float32),
                            jax.random.key(0))
    node_specs = lambda t: shd.param_specs(t, built.cfg, mesh,
                                           stacked_nodes=True)
    ssh = named(dsteps.TrainState(x=node_specs(shapes.x),
                                  h=node_specs(shapes.h),
                                  g_prev=node_specs(shapes.g_prev), step=P()))
    batches = [built.stream.batch_at(k) for k in (0, 1)]
    bsh = named(shd.batch_specs(batches[0], mesh, stacked_nodes=True))
    rep = NamedSharding(mesh, P())

    progs = {
        "init": jax.jit(lambda k: init_state(k, nodes, jnp.float32),
                        out_shardings=ssh),
        "warm": jax.jit(warm, in_shardings=(ssh, bsh), out_shardings=ssh,
                        donate_argnums=0, keep_unused=True),
        "auto": jax.jit(lambda s, b, T: step_auto(s, b, T, 0),
                        in_shardings=(ssh, bsh, rep),
                        out_shardings=(ssh, {"loss": rep}), donate_argnums=0),
        "dense": jax.jit(step_dense, in_shardings=(ssh, bsh, rep),
                         out_shardings=(ssh, {"loss": rep}),
                         donate_argnums=0),
    }
    args = {"batches": batches,
            "tensors": jax.tree.map(np.asarray, built.plan.tensors()),
            "weights": np.asarray(built.schedule.stacked(0, built.wps),
                                  np.float32),
            "state_shapes": shapes}
    return progs, args


def four_chip_phase(preset: str, devices, *, seq: int, R: int) -> dict:
    """One sharded step, auto (collective-permute) vs dense, on 4 devices."""
    _check(len(devices) == 4, f"{len(devices)} devices")
    mesh = Mesh(np.asarray(devices), ("data",))
    key = jax.random.key(0)
    # both programs at full f32 matmul precision, so the comparison sees
    # the lowering and not the MXU's default bf16 passes
    with jax.default_matmul_precision("highest"):
        progs, args = four_chip_programs(preset, mesh, seq=seq, R=R)
        b0, b1 = args["batches"]
        c0 = compile_counts()["compile_s"]
        t0 = time.perf_counter()
        state = progs["warm"](progs["init"](key), b0)
        auto = progs["auto"].lower(state, b1, args["tensors"]).compile()
        dense = progs["dense"].lower(state, b1, args["weights"]).compile()
        setup = time.perf_counter() - t0
        hlo = auto.as_text()
        _check("collective-permute" in hlo,
               "auto step has no collective-permute")

        sa, ma = auto(state, b1, args["tensors"])
        xa = jax.block_until_ready(sa.x)
        del sa
        for leaf in jax.tree.leaves(xa):
            shards = leaf.addressable_shards
            want = (leaf.shape[0] // 4,) + leaf.shape[1:]
            _check(len({s.device for s in shards}) == 4
                   and all(s.data.shape == want for s in shards),
                   f"x leaf {leaf.shape} is not split 4 ways by node")

        state = progs["warm"](progs["init"](key), b0)
        sd, md = dense(state, b1, args["weights"])
        xd = jax.block_until_ready(sd.x)
    la, ld = float(ma["loss"]), float(md["loss"])
    err = scale = 0.0
    for a, d in zip(jax.tree.leaves(xa), jax.tree.leaves(xd)):
        err = max(err, float(jnp.max(jnp.abs(a - d))))
        scale = max(scale, float(jnp.max(jnp.abs(d))))
    _check(math.isfinite(la) and abs(la - ld) <= 1e-5 * abs(ld),
           f"loss auto {la} vs dense {ld}")
    _check(err <= 1e-5 * scale, f"x auto vs dense: {err} at scale {scale}")
    return {"phase": "four_chips", "preset": preset, "nodes": 4, "R": R,
            "seq": seq, "loss_auto": la, "loss_dense": ld,
            "x_max_abs_diff": err, "x_scale": scale,
            "collective_permute_ops": hlo.count("collective-permute"),
            "setup_s": setup,
            "compile_s": compile_counts()["compile_s"] - c0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded auto-vs-dense step")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 TPUs, JAX found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        print(json.dumps(four_chip_phase("full", devices[:4],
                                         seq=TRAIN["seq"], R=TRAIN["R"])),
              flush=True)
    else:
        print(json.dumps(train_phase("full", **TRAIN)), flush=True)
        print(json.dumps(gossip_phase("full", require_kernel=True)),
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
