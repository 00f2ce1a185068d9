#!/usr/bin/env python3
"""One traced run of a cell, with a readable summary of its trace kept for
looking at by hand, and a small recorded trace for the tests.

    python3 benchmarks/chip/dump_trace.py --workload <cell> --seed <n> \\
        --out <summary.json.gz> [--events testdata/trace_events.json]

Runs ``run.py`` with ``--trace 1`` in this process and, as the trace is
read, writes every plane and line of it with its heaviest events (and the
metadata of the heaviest device ops) to ``--out``; with ``--events``, also
the reduction's events of the first two steps, in the form
``trace_reduce.load_json_events`` reads.
"""

import argparse
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (sets the compile cache before JAX is imported)
import trace_reduce  # noqa: E402


def _stats(obj) -> dict:
    out = {}
    try:
        items = list(obj.stats)
    except Exception:
        return out
    for k, v in items:
        out[str(k)] = v if isinstance(v, (int, float)) else str(v)
    return out


def describe(path: str, top: int = 40) -> dict:
    """A readable summary of a trace's planes, lines and heaviest events,
    with every stat of each and the metadata of the heaviest device ops,
    for looking at one trace by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    meta = trace_reduce.op_metadata(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            tot = {}
            for e in evs:
                tot.setdefault(e.name, [0, 0, None])
                tot[e.name][0] += int(e.duration_ns)
                tot[e.name][1] += 1
                if tot[e.name][2] is None:
                    tot[e.name][2] = _stats(e)
            heavy = sorted(tot.items(), key=lambda kv: -kv[1][0])[:top]
            lines.append({"line": line.name, "events": len(evs),
                          "first_start": int(evs[0].start_ns) if evs else None,
                          "heaviest": [{"name": k[:300], "ns": v[0],
                                        "count": v[1], "stats": v[2],
                                        "metadata": meta.get(k)}
                                       for k, v in heavy]})
        out.append({"plane": plane.name, "stats": _stats(plane),
                    "lines": lines})
    return {"planes": out}


def first_steps(events: dict, steps: int) -> dict:
    """The events of the first ``steps`` executions of the step program
    (a small recorded trace for tests)."""
    name = trace_reduce.step_module(events)
    runs = sorted(m["start"] for m in events["modules"] if m["name"] == name)
    if len(runs) <= steps:
        return events
    lo, hi = runs[0], runs[steps]
    keep = lambda e: e["start"] < hi and e["start"] + e["dur"] >= lo
    return {"devices": events["devices"],
            **{k: [e for e in events[k] if keep(e)]
               for k in ("ops", "modules", "host")}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--events", default=None)
    args = ap.parse_args(argv)
    load = trace_reduce.load

    def load_and_dump(path):
        events = load(path)
        with gzip.open(args.out, "wt") as f:
            json.dump(describe(path), f)
        if args.events:
            with open(args.events, "w") as f:
                json.dump(first_steps(events, 2), f)
        return events

    trace_reduce.load = load_and_dump
    return run.main(["--workload", args.workload, "--seed", args.seed,
                     "--seconds", "1", "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
