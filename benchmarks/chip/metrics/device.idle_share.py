"""Share of the traced window in which no operation ran on the device."""


def read(f):
    tr = f["trace"]
    if tr is None or not tr["busy_ns"]:
        return None
    return 100.0 * (1.0 - tr["busy_ns"] / tr["window_ns"])
