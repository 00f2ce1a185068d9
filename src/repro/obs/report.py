"""Render a run summary from a JSONL event log.

``python -m repro.obs.report <log.jsonl>`` prints the run header, the
driver's span tree (self time, median and max per step, the step of the
max, and the steps that compiled), per-metric stats with a unicode sparkline of
the series, and the optimality-gap section (measured best ||grad f||^2 vs
the paper's lower-bound floor for the run's cell).  Everything is computed
from the log alone — no jax, no re-execution — so it works on logs shipped
as CI artifacts.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

from .metrics import OBS_METRICS, read_events

_BARS = "▁▂▃▄▅▆▇█"


def sparkline(vals, width: int = 32) -> str:
    """Downsample ``vals`` to ``width`` buckets of unicode bars."""
    vals = [v for v in vals if v is not None]
    if not vals:
        return ""
    if len(vals) > width:
        step = len(vals) / width
        vals = [vals[int(i * step)] for i in range(width)]
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _BARS[0] * len(vals)
    return "".join(_BARS[min(len(_BARS) - 1,
                             int((v - lo) / (hi - lo) * len(_BARS)))]
                   for v in vals)


def _fmt(v, nd: int = 4) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}g}"
    return str(v)


def _series(steps, key):
    return [s[key] for s in steps if s.get(key) is not None]


def _stats(vals) -> Optional[dict]:
    if not vals:
        return None
    return {"first": vals[0], "last": vals[-1],
            "min": min(vals), "max": max(vals), "n": len(vals)}


def _span_order(phases: dict) -> list:
    """(depth, name) of every span, children under their parent, each
    level by total time."""
    kids: dict = {}
    for name, p in phases.items():
        parent = p.get("parent")
        kids.setdefault(parent if parent in phases else None, []).append(name)
    out = []

    def walk(parent, depth):
        for name in sorted(kids.get(parent, ()),
                           key=lambda n: -phases[n]["total_sec"]):
            out.append((depth, name))
            walk(name, depth + 1)
    walk(None, 0)
    return out


def span_table(phases: dict) -> list:
    """The span tree as text lines: calls, total and self seconds, median
    and max ms per call (with the step of the max), and the steps whose
    span compiled.  Logs without self/median/max show the mean."""
    lines = [f"  {'span':<20}{'calls':>7}{'total s':>10}{'self s':>10}"
             f"{'median ms':>11}{'max ms':>10}{'at step':>9}  compiled at"]
    for depth, name in _span_order(phases):
        p = phases[name]
        med = p.get("median_ms", p.get("mean_ms"))
        steps = p.get("compiled_steps") or []
        mx = p.get("max_ms")
        lines.append(
            f"  {'  ' * depth + name:<20}{p['count']:>7}"
            f"{p['total_sec']:>10.4f}"
            f"{p.get('self_sec', p['total_sec']):>10.4f}{med:>11.3f}"
            f"{'-' if mx is None else f'{mx:.3f}':>10}"
            f"{_fmt(p.get('max_step')):>9}"
            f"  {', '.join(str(k) for k in steps) or '-'}")
    return lines


def render(events: list, width: int = 32) -> str:
    """The full text report for one event log."""
    meta = next((e for e in events if e.get("event") == "meta"), {})
    steps = [e for e in events if e.get("event") == "step"]
    evals = [e for e in events if e.get("event") == "eval"]
    summary = next((e for e in events if e.get("event") == "summary"), {})
    lines: list[str] = []

    title = meta.get("name") or meta.get("algo") or "run"
    lines.append(f"== repro.obs report: {title} ==")
    head = {k: v for k, v in meta.items()
            if k not in ("event", "name") and not isinstance(v, (dict, list))}
    if head:
        lines.append("  " + "  ".join(f"{k}={_fmt(v)}"
                                      for k, v in sorted(head.items())))
    if steps:
        secs = _series(steps, "sec")
        lines.append(f"  steps recorded: {len(steps)}   "
                     f"T: {steps[-1].get('t', '-')}   "
                     f"step time: {_fmt(sum(secs) / len(secs))}s mean"
                     if secs else f"  steps recorded: {len(steps)}")

    phases = summary.get("phases") or {}
    if phases:
        lines.append("")
        lines.append("-- phases " + "-" * (width + 18))
        lines.extend(span_table(phases))

    metric_keys = ["loss", *OBS_METRICS]
    shown = [k for k in metric_keys if _series(steps, k)]
    if shown:
        lines.append("")
        lines.append("-- metrics " + "-" * (width + 17))
        for key in shown:
            vals = _series(steps, key)
            st = _stats(vals)
            lines.append(f"  {key:<17} {sparkline(vals, width):<{width}} "
                         f"last={_fmt(st['last'])} min={_fmt(st['min'])} "
                         f"max={_fmt(st['max'])}")
    if evals:
        vals = [e["value"] for e in evals]
        st = _stats(vals)
        lines.append(f"  {'eval':<17} {sparkline(vals, width):<{width}} "
                     f"last={_fmt(st['last'])} min={_fmt(st['min'])} "
                     f"max={_fmt(st['max'])}")

    opt = summary.get("optimality")
    if opt:
        lines.append("")
        lines.append("-- optimality gap " + "-" * (width + 10))
        lines.append(f"  cell: {opt.get('cell', '-')}   "
                     f"bound: {opt.get('bound', 'paper')}   "
                     f"n={opt.get('n', '-')} beta={_fmt(opt.get('beta'))}")
        lines.append(f"  T={opt.get('T', '-')}   "
                     f"floor={_fmt(opt.get('floor'))}   "
                     f"best ||grad f||^2={_fmt(opt.get('best_grad_sq'))}")
        gap = opt.get("gap_ratio")
        slope = opt.get("rate_slope")
        lines.append(f"  gap ratio (measured / floor): {_fmt(gap)}   "
                     f"empirical slope d log/d logT: {_fmt(slope)}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.report",
        description="Render a run summary from a repro.obs JSONL event log")
    ap.add_argument("log", help="path to the .jsonl event log")
    ap.add_argument("--width", type=int, default=32,
                    help="sparkline width (default 32)")
    ap.add_argument("--json", action="store_true",
                    help="dump the summary event as JSON instead")
    args = ap.parse_args(argv)
    events = read_events(args.log)
    try:
        if args.json:
            summary = next((e for e in events
                            if e.get("event") == "summary"), {})
            print(json.dumps(summary, indent=1))
        else:
            print(render(events, width=args.width))
    except BrokenPipeError:  # e.g. piped into head
        sys.stderr.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
