"""Device time per step of the operations under the engine's ``obs_grad``
name scope (loss and gradients of every node's microbatches, and the
clip)."""


def read(f):
    tr = f["trace"]
    if tr is None or not tr["scope_ns"]["obs_grad"]:
        return None
    return tr["scope_ns"]["obs_grad"] / tr["steps"] / 1e6
