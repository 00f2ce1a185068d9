"""The device allocator's peak_bytes_in_use after the window, in GB."""


def read(f):
    b = f["memory_peak_bytes"]
    return b / 1e9 if b else None
