"""Device time per step of the operations under the engine's ``obs_mix``
name scope (every gossip round of x and of the tracker h)."""


def read(f):
    tr = f["trace"]
    if tr is None or not tr["scope_ns"]["obs_mix"]:
        return None
    return tr["scope_ns"]["obs_mix"] / tr["steps"] / 1e6
