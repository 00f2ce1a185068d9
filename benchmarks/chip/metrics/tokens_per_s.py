"""Input positions that every node's gradient oracle consumed in the
window, over the host-clock time between the ready stamps that bound it."""


def read(f):
    r = f["ready"]
    return f["positions_per_step"] * (len(r) - 1) / (r[-1] - r[0])
