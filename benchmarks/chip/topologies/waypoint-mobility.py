"""Random-waypoint mobility in the unit square, as the program documents
it: node positions move in straight legs of 8 rounds between waypoints,
the waypoints of leg l drawn uniform from
``default_rng(SeedSequence((seed, 0x3A7, l))).random((n, 2))``; at round t
of a leg the position is a + (b - a) * r / 8. Nodes within ``radius`` are
linked (unit-disk graph), and the link (i, j) weighs
1 / (1 + max(deg i, deg j)) (Metropolis), the rest of each row staying on
the diagonal."""

import numpy as np

LEG_ROUNDS = 8
_TAG = 0x3A7


def _waypoints(n, seed, leg):
    rng = np.random.default_rng(np.random.SeedSequence((seed, _TAG, leg)))
    return rng.random((n, 2))


def weights(n: int, t: int, seed: int, spec: dict) -> np.ndarray:
    leg, r = divmod(t, LEG_ROUNDS)
    a, b = _waypoints(n, seed, leg), _waypoints(n, seed, leg + 1)
    pos = a + (b - a) * (r / LEG_ROUNDS)
    d2 = ((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1)
    link = d2 <= spec["radius"] ** 2
    np.fill_diagonal(link, False)
    deg = link.sum(axis=1)
    w = np.where(link, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])),
                 0.0)
    w[np.diag_indices(n)] = 1.0 - w.sum(axis=1)
    return w
