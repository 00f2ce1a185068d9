"""Weights and data made from the run's seed.

Weights: the benchmark, not the program, makes them. The program's
``model.init`` is replaced by ``make_weights`` for the length of a run
(it runs only in set-up), so the reference rebuilds the same numbers from
the seed without taking anything that the program made. They come from
one jitted call on the device, in float32.

Data: the timed path draws its batches with the program's own token
stream. ``Stream`` draws the same batches for the reference, written from
the stream's documented draw, not from its code.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# the fan-in axes of each matrix, counted from the end of its shape; the
# leading axes of a stacked-layer leaf are layers
_FAN_IN = {"wq": (-3,), "wk": (-3,), "wv": (-3,), "wi": (-2,), "wg": (-2,),
           "embedding": (-1,)}
_BIAS_STD = 0.02


# the weights' stream: one the token stream, which folds the step into the
# seed's key, never reaches
_WEIGHTS = 2 ** 32 - 1


def _leaf_init(path, shape, key):
    names = [getattr(p, "key", getattr(p, "name", None)) for p in path]
    last, parent = names[-1], names[-2] if len(names) > 1 else None
    if last == "scale":
        return jnp.ones(shape, jnp.float32)
    if last == "bias":
        return jnp.zeros(shape, jnp.float32)
    if last in ("bq", "bk", "bv"):
        return _BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    if last == "wo":
        # attention out-projection (..., H, hd, D); MLP down-projection (..., F, D)
        axes = (-2,) if parent == "mlp" else (-3, -2)
    else:
        axes = _FAN_IN[last]
    fan_in = math.prod(shape[a] for a in axes)
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def weights_key(seed: int) -> jax.Array:
    return jax.random.fold_in(jax.random.key(seed), _WEIGHTS)


def weight_leaves(shapes, key) -> list:
    """The weights' leaves, in the order of ``jax.tree.leaves(shapes)``,
    from ``weights_key(seed)``; traceable."""
    paths, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [_leaf_init(p, s.shape, jax.random.fold_in(key, i))
            for i, (p, s) in enumerate(paths)]


def make_weights(shapes, seed: int):
    """float32 weights shaped like ``shapes`` (a pytree of ShapeDtypeStruct
    in the program's parameter layout), in one jitted call."""
    treedef = jax.tree.structure(shapes)
    gen = jax.jit(lambda key: jax.tree.unflatten(
        treedef, weight_leaves(shapes, key)))
    return gen(weights_key(seed))


class Stream:
    """Per-step batches ``(n, R, b, ...)`` as the program's synthetic token
    stream draws them (iid, full vocabulary): with ``k = fold_in(key(seed),
    step)``, token ids ``randint(k, (n, R, b, seq), 0, vocab)`` and, for the
    encoder-decoder, frame embeddings ``0.02 * normal(fold_in(k, 2), (n, R,
    b, frames, width))``. ``batch_at(k)`` is the same for the same seed and
    k."""

    def __init__(self, *, seed, nodes, rounds, batch, seq, vocab,
                 frames=0, width=0):
        self.shape = (nodes, rounds, batch)
        self.seq, self.vocab = seq, vocab
        self.frames, self.width = frames, width
        self.key = jax.random.key(seed)
        self._gen = jax.jit(self._make)

    def _make(self, key):
        out = {"tokens": jax.random.randint(
            key, self.shape + (self.seq,), 0, self.vocab, jnp.int32)}
        if self.frames:
            out["frames"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, 2),
                self.shape + (self.frames, self.width), jnp.float32)
        return out

    def batch_at(self, step: int) -> dict:
        return self._gen(jax.random.fold_in(self.key, step))
