import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production mesh, record memory/cost analysis and the collective
schedule (deliverable e; feeds EXPERIMENTS.md §Dry-run / §Roofline).

Cost accounting: XLA's HloCostAnalysis counts while-loop (lax.scan) bodies
ONCE, so the scan-over-layers lowering under-reports FLOPs/bytes/collective
volume.  The dry-run therefore does two things per combination:

  1. compiles the FULL config with scan-over-layers — this is the artifact
     that proves the (arch x shape x mesh) lowers, and its memory_analysis
     is the realistic per-device footprint;
  2. compiles two small UNROLLED probes (1 and 2 pattern-units, every scan
     replaced by a Python loop) and extrapolates cost linearly in the unit
     count: cost(L) = c1 + (c2 - c1) * (units - 1) [+ pro-rated remainder].
     Extrapolation is exact because pattern units are identical subgraphs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""

import argparse
import dataclasses
import json
import re
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.dist import sharding as shd
from repro.dist import steps as dsteps
from repro.launch import mesh as meshlib
from repro.models import build, model as modellib

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "c64": 8}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def config_for_shape(cfg, shape_name: str):
    """Shape-specific config adjustments: long_500k requires sub-quadratic
    attention -> enable the sliding-window variant (4096) on archs whose
    attention is otherwise full-causal.  SSM archs need nothing."""
    if shape_name == "long_500k" and cfg.num_heads and not cfg.window:
        return dataclasses.replace(cfg, window=4096)
    return cfg


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the optimized HLO."""
    out = {c: 0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    pat = re.compile(
        r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+(" + "|".join(COLLECTIVES) + r")\(")
    tuple_pat = re.compile(
        r"=\s+\(([^)]+)\)\s+(" + "|".join(COLLECTIVES) + r")\(")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if m:
            dtype, dims, op = m.groups()
            nbytes = _DTYPE_BYTES.get(dtype, 4)
            size = 1
            for d in dims.split(","):
                if d:
                    size *= int(d)
            out[op] += size * nbytes
            counts[op] += 1
            continue
        m = tuple_pat.search(line)
        if m:
            parts, op = m.groups()
            for piece in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]", parts):
                dtype, dims = piece.groups()
                nbytes = _DTYPE_BYTES.get(dtype, 4)
                size = 1
                for d in dims.split(","):
                    if d:
                        size *= int(d)
                out[op] += size * nbytes
            counts[op] += 1
    return {"per_op": out, "counts": counts, "total_bytes": sum(out.values())}


# ---------------------------------------------------------------------------
# Lowering (shared by the full compile and the cost probes)
# ---------------------------------------------------------------------------

def _lower(cfg, shape, mesh, *, R: int, gamma: float, unroll_step: bool,
           train_kwargs: dict | None = None):
    """Lower the appropriate step for ``shape.kind`` under ``mesh``."""
    model = build(cfg)
    dtype = jnp.dtype(cfg.dtype)
    tkw = dict(train_kwargs or {})
    if shape.kind == "train":
        n = shd.n_nodes(mesh)
        b = max(1, shape.global_batch // (n * R))
        tmpl = modellib.train_batch_template(cfg, b, shape.seq_len, dtype)
        batch = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n, R) + s.shape, s.dtype), tmpl)
        init_state, _, train_step = dsteps.make_train_step(
            model, cfg, gamma=gamma, R=R, unroll=unroll_step, **tkw)
        state = jax.eval_shape(lambda: init_state(jax.random.key(0), n, dtype))
        if tkw.get("gossip_impl") == "sun":
            weights = jax.ShapeDtypeStruct((2 * R, n), jnp.float32)
        else:
            weights = jax.ShapeDtypeStruct((2 * R, n, n), jnp.float32)
        state_specs = dsteps.TrainState(
            x=shd.param_specs(state.x, cfg, mesh, stacked_nodes=True),
            h=shd.param_specs(state.h, cfg, mesh, stacked_nodes=True),
            g_prev=shd.param_specs(state.g_prev, cfg, mesh, stacked_nodes=True),
            step=P())
        bspecs = shd.batch_specs(batch, mesh, stacked_nodes=True)
        return jax.jit(train_step, in_shardings=(state_specs, bspecs, P()),
                       out_shardings=(state_specs, {"loss": P()})).lower(
            state, batch, weights)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), dtype))
    pspecs = shd.param_specs(params, cfg, mesh)
    is_audio = cfg.arch_type == "audio"
    if shape.kind == "prefill":
        B = shape.global_batch
        tmpl = modellib.train_batch_template(cfg, B, shape.seq_len, dtype)
        cache = jax.eval_shape(lambda: model.init_cache(B, shape.seq_len, dtype))
        cspecs = shd.param_specs(cache, cfg, mesh, audio_cache=is_audio)
        bspecs = shd.batch_specs(tmpl, mesh, stacked_nodes=False)
        step = dsteps.make_prefill_step(model, cfg)
        return jax.jit(step, in_shardings=(pspecs, bspecs, cspecs)).lower(
            params, tmpl, cache)
    B = shape.global_batch
    token, cache, pos = modellib.decode_templates(cfg, B, shape.seq_len, dtype)
    cspecs = shd.param_specs(cache, cfg, mesh, audio_cache=is_audio)
    tok_spec = shd.batch_specs({"t": token}, mesh, stacked_nodes=False)["t"]
    step = dsteps.make_serve_step(model, cfg)
    return jax.jit(step, in_shardings=(pspecs, tok_spec, cspecs, P())).lower(
        params, token, cache, pos)


def _probe_cfg(cfg, k_units: int):
    pat = len(cfg.pattern)
    repl = dict(num_layers=k_units * pat, unroll=True,
                q_chunk=10_000_000, scan_chunk=10_000_000)
    if cfg.encoder_layers:
        repl["encoder_layers"] = k_units
    return dataclasses.replace(cfg, **repl)


def _costs_of(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    coll = parse_collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll}


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              R: int = 2, gamma: float = 1e-3, verbose: bool = True,
              probe: bool = True, cfg_transform=None,
              train_kwargs: dict | None = None, mesh_builder=None) -> dict:
    cfg = config_for_shape(configs.get(arch), shape_name)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    shape = configs.INPUT_SHAPES[shape_name]
    mesh = (mesh_builder() if mesh_builder is not None
            else meshlib.make_production_mesh(multi_pod=multi_pod))
    t0 = time.time()

    with jax.set_mesh(mesh):
        compiled = _lower(cfg, shape, mesh, R=R, gamma=gamma,
                          unroll_step=False, train_kwargs=train_kwargs).compile()
        probe_costs = None
        if probe:
            c1 = _costs_of(_lower(_probe_cfg(cfg, 1), shape, mesh, R=R,
                                  gamma=gamma, unroll_step=True,
                                  train_kwargs=train_kwargs).compile())
            c2 = _costs_of(_lower(_probe_cfg(cfg, 2), shape, mesh, R=R,
                                  gamma=gamma, unroll_step=True,
                                  train_kwargs=train_kwargs).compile())
            probe_costs = (c1, c2)

    t1 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll_scan = parse_collective_bytes(compiled.as_text())

    units, rem = cfg.units_and_rem
    if probe_costs:
        c1, c2 = probe_costs
        scale = (units - 1) + rem / len(cfg.pattern)

        def extrap(f1, f2):
            return f1 + (f2 - f1) * scale

        flops = extrap(c1["flops"], c2["flops"])
        nbytes = extrap(c1["bytes"], c2["bytes"])
        coll_total = extrap(c1["coll"]["total_bytes"], c2["coll"]["total_bytes"])
        coll_per_op = {k: extrap(c1["coll"]["per_op"][k], c2["coll"]["per_op"][k])
                       for k in c1["coll"]["per_op"]}
        collectives = {"per_op": coll_per_op, "total_bytes": coll_total,
                       "counts_1unit": c1["coll"]["counts"]}
    else:
        flops = float(cost.get("flops", -1))
        nbytes = float(cost.get("bytes accessed", -1))
        collectives = coll_scan

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": ("x".join(str(mesh.shape[a]) for a in mesh.axis_names)
                 if mesh_builder is not None
                 else ("2x16x16" if multi_pod else "16x16")),
        "devices": int(mesh.size),
        "compile_seconds": round(t1 - t0, 1),
        "flops": flops,
        "bytes_accessed": nbytes,
        "flops_scanbody": float(cost.get("flops", -1)),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
            "output_bytes": getattr(mem, "output_size_in_bytes", -1),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "output_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0)),
        },
        "collectives": collectives,
        "collectives_scanbody": coll_scan,
    }
    if verbose:
        print(json.dumps(result, indent=2))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(configs.INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--R", type=int, default=2)
    args = ap.parse_args()

    archs = [a for a in configs.names()] if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(configs.INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            tag = f"{arch}_{shape}_{'2x16x16' if args.multi_pod else '16x16'}"
            try:
                res = lower_one(arch, shape, multi_pod=args.multi_pod,
                                R=args.R, verbose=False,
                                probe=not args.no_probe)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=2)
                print(f"OK   {tag}: compile={res['compile_seconds']}s "
                      f"flops={res['flops']:.3e} "
                      f"coll={res['collectives']['total_bytes']:.3e}B",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                failures.append((tag, str(e)[:200]))
                print(f"FAIL {tag}: {str(e)[:200]}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: "
                         + "; ".join(t for t, _ in failures))
    print("all dry-runs compiled")


if __name__ == "__main__":
    main()
