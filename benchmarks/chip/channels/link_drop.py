"""iid link loss, as the program documents it: at round t every
undirected link fails with probability ``rate``, from one uniform per
link drawn by ``default_rng(SeedSequence((seed + 101, 0xB0, t)))`` as the
upper triangle of an (n, n) draw; the link survives where the uniform is
at least ``rate``."""

import numpy as np

_SEED_OFFSET = 101
_TAG = 0xB0


def survives(n: int, t: int, seed: int, rate: float) -> np.ndarray:
    rng = np.random.default_rng(
        np.random.SeedSequence((seed + _SEED_OFFSET, _TAG, t)))
    u = np.triu(rng.random((n, n)), 1)
    keep = (u + u.T) >= rate
    np.fill_diagonal(keep, True)
    return keep
