#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs the cell's decentralized training through ``repro.exp.run`` on the
TPU this process holds, measures ``--seconds`` of steady steps (or, with
``--trace 1``, profiles a few), checks the first steps against the plain
reference, and prints one JSON object as its last line of stdout, with
the compared numbers and their limits as the last lines of stderr. Exits
non-zero and prints no result without a TPU, with fewer chips than the
cell asks for, or with a device that ``peaks.json`` does not list.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# JAX's compile cache lives in this checkout at a fixed path, whatever
# directory the environment names (set before JAX is imported, which reads
# it): a checkout's first run compiles, every later run there loads
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
os.makedirs(CACHE_DIR, exist_ok=True)  # JAX writes no entry without it
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    import harness  # the program is imported here: it must be present
    entry = harness.bench_entry(bench, args.workload)

    import jax
    t_import = time.perf_counter() - T0
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        print(f"run.py: {args.workload} needs {entry['chips']} TPU chip(s), "
              f"JAX found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    peaks = harness.peaks_for(devices[0].device_kind)

    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cache = enable_compile_cache()
    if not cache == CACHE_DIR == jax.config.jax_compilation_cache_dir:
        raise RuntimeError(f"compile cache at {cache!r}, not {CACHE_DIR!r}")
    print(json.dumps({"phase": "start", "compile_cache": cache,
                      "imported_s": t_import,
                      "devices_s": time.perf_counter() - T0}), flush=True)
    out = harness.run_cell(bench, args.workload, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           t0=T0, peaks=peaks)
    for name, c in out["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
