"""Median, over the window's steps, of the program's ``record.readback``
span: the record hook's host work once the step is ready (the loss, the
consensus distance, the console line), in ms. The spans come from the
program's own ring buffer (``repro.obs.trace.spans()``), which this
process filled during the run. A window step is one whose
``record.sync`` span ends in (ready[0], ready[-1]]: that end is the
step's ready stamp. None where the program records no such span."""

import statistics


def read(f):
    try:
        from repro.obs import trace
    except ImportError:
        return None
    spans = getattr(trace, "spans", None)
    ready = f.get("ready")
    if spans is None or not ready:
        return None
    lo, hi = ready[0], ready[-1]
    got = spans()
    steps = {s.k for s in got
             if s.name == "record.sync" and lo < s.end <= hi}
    vals = [s.dur for s in got if s.name == "record.readback"
            and s.k in steps and s.start >= lo]
    return 1e3 * statistics.median(vals) if vals else None
