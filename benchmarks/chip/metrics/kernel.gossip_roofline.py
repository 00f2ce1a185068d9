"""The fused Pallas gossip kernel's share of its roofline: the least time
its calls could take on this chip -- the larger of the bytes they must
move over the HBM peak and their FLOPs over the bf16 peak
(counting.gossip_kernel_cost) -- over the device time of its events. At
these sizes the bytes bound it: an (n, D) f32 state read and written
once per call."""

import re

import counting

KERNEL = re.compile(r"^gossip_mix(\.\d+)?$")


def read(f):
    tr = f["trace"]
    if tr is None or not f["peaks"]:
        return None
    rows = [r for r in tr["op_table"] if KERNEL.match(r[0])]
    ns = sum(r[2] for r in rows)
    if not ns:
        return None
    t, p = f["traffic"], f["peaks"]
    cost = counting.gossip_kernel_cost(t["nodes"], f["state_entries"], t["R"])
    least = max(cost["bytes"] / p["hbm_bytes_per_s"],
                cost["flops"] / p["bf16_flops_per_s"])
    return 100.0 * least * sum(r[3] for r in rows) / (ns / 1e9)
