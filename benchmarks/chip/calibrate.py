#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chip (the benchmark's own runs never run this):

* the program's first steps against the reference, on each ``--seeds``;
* the control -- the reference computed in bfloat16 at the default matmul
  precision, one step below the float32 the configuration states -- put
  in the program's place, on each ``--control-seeds``;
* each planted fault (``faults.py``) on each ``--fault-seeds``.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,3 --control-seeds 1,2,3 --fault-seeds 1,2,3

Prints one JSON line per reading and a summary line last: the largest
reading of the program, the smallest of the control and of each fault.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)
# the compile cache the benchmark's runs use (see run.py)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)

import faults  # noqa: E402
import harness  # noqa: E402


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def calibrate(bench, workload, *, seeds, control_seeds, fault_seeds,
              faults_to_plant, preset="full", root=HERE, log=print):
    import jax.numpy as jnp

    cell = harness.find_cell(bench, workload, root)
    cfg, traffic = cell.cfg, cell.traffic
    refs = {}

    def program(seed, fault=None):
        plan = harness.Plan(seconds=0, trace=False, check_only=True)
        spec = harness.make_spec(cfg, traffic, seed, preset)
        with faults.planted(fault) if fault else contextlib.nullcontext():
            built, shapes = harness.drive(
                spec, cfg, traffic, plan, preset=preset, rule=cell.rule,
                model_wrap=faults.model_wrap(fault) if fault else None)
        gc.collect()
        return plan.got, built, shapes

    def ref(seed, built, shapes, **low):
        key = (seed, bool(low))
        if key not in refs:
            refs[key] = harness.reference_readings(
                cell, built.cfg, shapes, seed, preset=preset, **low)
        return refs[key]

    rows = []

    def note(kind, seed, r, **extra):
        rows.append((kind, r))
        log(json.dumps({"kind": kind, "seed": seed, "readings": r, **extra}))

    for seed in seeds:
        got, built, shapes = program(seed)
        note("program", seed, harness.reference.readings(
            got, ref(seed, built, shapes)), losses=got["loss"])
    for seed in control_seeds:
        built, shapes = harness.scenario(cfg, traffic, seed, preset)
        low = ref(seed, built, shapes, dtype=jnp.bfloat16, precision=None)
        note("control", seed, harness.reference.readings(
            low, ref(seed, built, shapes)))
    for fault in (f for f in faults_to_plant if faults.applies(f, traffic)):
        for seed in fault_seeds:
            got, built, shapes = program(seed, fault)
            note(fault, seed, harness.reference.readings(
                got, ref(seed, built, shapes)))
    summary = {}
    for kind, r in rows:
        agg = max if kind == "program" else min
        cur = summary.setdefault(kind, dict(r))
        for k, v in r.items():
            cur[k] = agg(cur[k], v)
    log(json.dumps({"summary": summary,
                    "elapsed_s": time.perf_counter() - T0}))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", default=",".join(faults.FAULTS))
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    calibrate(bench, args.workload, seeds=args.seeds,
              control_seeds=args.control_seeds, fault_seeds=args.fault_seeds,
              faults_to_plant=[f for f in args.faults.split(",") if f],
              log=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
