"""The 90th percentile, over every step of the window, of the host-clock
interval between consecutive ready stamps."""

import statistics


def read(f):
    r = f["ready"]
    gaps = [(b - a) * 1e3 for a, b in zip(r, r[1:])]
    return statistics.quantiles(gaps, n=10, method="inclusive")[8]
