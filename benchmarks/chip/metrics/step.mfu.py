"""Model FLOPs of the traced steps (counting.step_flops: forward and
backward matmuls, no recomputation) over the traced window, as a share of
the chip's bf16 peak: the f32 matmuls run as bf16 MXU passes at the
default precision."""


def read(f):
    tr = f["trace"]
    if tr is None or not f["peaks"]:
        return None
    rate = f["flops_per_step"] * tr["steps"] / (tr["window_ns"] / 1e9)
    return 100.0 * rate / f["peaks"]["bf16_flops_per_s"]
