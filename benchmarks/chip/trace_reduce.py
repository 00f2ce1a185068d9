"""From a profiler trace of a few steady steps to per-layer numbers.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps three kinds of event, each a dict ``{"name", "start", "dur", "scope"}``
with times in nanoseconds on one clock:

* ``ops``: the device's XLA operations (``scope`` is the operation's
  name-scope path, which carries the program's ``obs_`` scopes, such as
  ``obs_grad`` and ``obs_mix``);
* ``modules``: executions of whole XLA programs on the device;
* ``host``: the host threads' events (Python calls, dispatch).

``reduce(events)`` bounds the window by the step program's own executions:
from the start of its first execution to the start of its last, so the
window holds whole steps, each with everything that ran between two
steps. Everything it returns is a sum over that window.
"""

from __future__ import annotations

import glob
import json
import os
import re

# read by metrics/, so present in every reduction (at 0 where absent)
SCOPES = ("obs_grad", "obs_mix")
_OBS = re.compile(r"\bobs_\w+")


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of a protobuf message in ``buf[i:end]``;
    length-delimited values come as (start, end) offsets."""
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_metadata(path: str, prefix: str = "/device:TPU:") -> dict:
    """The device planes' op metadata from the raw ``.xplane.pb`` (XSpace:
    planes=1; XPlane: name=2, event_metadata=4, stat_metadata=5;
    XEventMetadata: name=2, display_name=4, stats=5; XStat: metadata_id=1,
    str_value=5): each op's name (and display name) -> its string stats
    by stat name, such as the ``tf_op`` name-scope path that
    ``ProfileData`` leaves out."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pn, pv in _fields(buf, *plane):
            if pn == 2:
                name = _text(buf, pv)
            elif pn in (4, 5):  # map entries: key=1, value=2
                for en, ev in _fields(buf, *pv):
                    if en != 2:
                        continue
                    if pn == 4:
                        metas.append(ev)
                    else:
                        sid = sname = None
                        for sn, sv in _fields(buf, *ev):
                            if sn == 1:
                                sid = sv
                            elif sn == 2:
                                sname = _text(buf, sv)
                        stat_names[sid] = sname
        if not name.startswith(prefix):
            continue
        for span in metas:
            names, stats = [], {}
            for mn, mv in _fields(buf, *span):
                if mn in (2, 4):
                    names.append(_text(buf, mv))
                elif mn == 5:
                    sid = val = None
                    for sn, sv in _fields(buf, *mv):
                        if sn == 1:
                            sid = sv
                        elif sn == 5:
                            val = _text(buf, sv)
                    if val is not None:
                        stats[stat_names.get(sid, str(sid))] = val
            for n in names:
                if n:
                    out[n] = stats
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _short(name: str) -> str:
    """``%fusion.336 = f32[...] fusion(...)`` -> ``fusion.336``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """The device's ops and program executions, and the host's events.
    An op's ``name`` is its HLO instruction's name and its ``scope`` its
    metadata (the ``tf_op`` name-scope path among it)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    meta = op_metadata(path)
    ev = {"ops": [], "modules": [], "host": [], "devices": 0}
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:")
        if device:
            ev["devices"] += 1
        for line in plane.lines:
            if device and line.name == "XLA Ops":
                kind = "ops"
            elif device and line.name == "XLA Modules":
                kind = "modules"
            elif plane.name == "/host:CPU":
                kind = "host"
            else:
                continue
            for e in line.events:
                row = {"name": e.name, "start": int(e.start_ns),
                       "dur": int(e.duration_ns), "scope": ""}
                if kind == "ops":
                    st = meta.get(e.name, {})
                    row["name"] = _short(e.name)
                    row["scope"] = st.get("tf_op", "")
                elif kind == "host":
                    row["line"] = line.name
                ev[kind].append(row)
    return ev


def load_json_events(path: str) -> dict:
    """Events as ``load`` returns them, saved as JSON (a recorded trace)."""
    with open(path) as f:
        return json.load(f)


def _union(intervals):
    """Total length and the merged (start, end) list of intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def step_module(events: dict) -> str | None:
    """The program that took the most device time: the training step."""
    tot = {}
    for m in events["modules"]:
        tot[m["name"]] = tot.get(m["name"], 0) + m["dur"]
    return max(tot, key=tot.get) if tot else None


def reduce(events: dict, *, top: int = 10) -> dict | None:
    """Per-window sums: ``steps`` whole steps in ``window_ns``; ``busy_ns``
    (union of device ops); ``scope_ns``, for every name scope that starts
    with ``obs_`` and appears in the window's ops (and for ``SCOPES``
    always), the union of the ops whose ``tf_op`` path holds that name (a
    scope whose name holds another's counts under both); ``op_table``, each
    op name with its scope, device time and count; the ``top`` ops by time
    and the ``top`` longest idle gaps, each named by the innermost host
    event around its middle. None when the trace holds fewer than two
    executions of the step."""
    name = step_module(events)
    runs = sorted(m["start"] for m in events["modules"] if m["name"] == name)
    if len(runs) < 2:
        return None
    lo, hi = runs[0], runs[-1]
    ops = []
    for o in events["ops"]:
        s, e = max(o["start"], lo), min(o["start"] + o["dur"], hi)
        if e > s:
            ops.append((s, e, o))
    busy, merged = _union([(s, e) for s, e, _ in ops])
    # a union per scope: a loop's op and the ops of its body nest
    names = set(SCOPES).union(*(_OBS.findall(o["scope"]) for _, _, o in ops))
    scope_ns = {sc: _union([(s, e) for s, e, o in ops if sc in o["scope"]])[0]
                for sc in sorted(names)}
    table = {}
    for s, e, o in ops:
        row = table.setdefault(o["name"], [o["scope"], 0, 0])
        row[1] += e - s
        row[2] += 1
    gaps, prev = [], lo
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    # name a gap by the Python-level call the host was in, where traced
    host = [h for h in events["host"] if h.get("line", "").startswith(
        "python")] or events["host"]
    return {"step_program": name, "steps": len(runs) - 1,
            "window_ns": hi - lo, "busy_ns": busy, "scope_ns": scope_ns,
            "op_table": [(n, sc, ns, c) for n, (sc, ns, c) in table.items()],
            "top_ops": sorted(((n, r[1]) for n, r in table.items()),
                              key=lambda kv: -kv[1])[:top],
            "idle_gaps": [(_host_at(host, (a + b) // 2), b - a)
                          for a, b in gaps[:top]]}


def _host_at(host: list, t: int) -> str:
    inner = None
    for h in host:
        if h["start"] <= t <= h["start"] + h["dur"] and (
                inner is None or h["dur"] < inner["dur"]):
            inner = h
    return inner["name"] if inner is not None else "no host event"
