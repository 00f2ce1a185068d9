"""The one-peer exponential graph: at round t node i averages with node
i XOR 2^(t mod log2 n), weights 1/2 and 1/2 (for n = 2, the mean)."""

import numpy as np


def weights(n: int, t: int, seed: int, spec: dict) -> np.ndarray:
    hops = max(1, n.bit_length() - 1)
    peer = np.arange(n) ^ (1 << (t % hops))
    w = np.zeros((n, n))
    w[np.arange(n), np.arange(n)] += 0.5
    w[np.arange(n), peer] += 0.5
    return w
