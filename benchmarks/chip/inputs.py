"""Weights and data made from the run's seed.

Weights: the benchmark, not the program, makes them. The program's
``model.init`` is replaced by ``make_weights`` for the length of a run
(it runs only in set-up), so the reference rebuilds the same numbers from
the seed without taking anything that the program made. They come from
one jitted call on the device, in float32, each leaf drawn by the
configuration's ``WeightRule``: what its module states in ``WEIGHTS``,
and the built-in rule for the rest.

Data: the timed path draws its batches with the program's own token
stream. ``Stream`` draws the same batches for the reference, written from
the stream's documented draw, not from its code.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_BIAS_STD = 0.02


def _bias(shape, key):
    return _BIAS_STD * jax.random.normal(key, shape, jnp.float32)


# How each leaf is drawn, by its name or by "parent/name": a tuple of
# fan-in axes counted from the end of its shape (N(0, 1/fan_in); the
# leading axes of a stacked-layer leaf are layers), a constant, or
# ``draw(shape, key)``. "parent/name" comes before "name".
BUILT_IN = {
    "scale": 1.0, "bias": 0.0, "bq": _bias, "bk": _bias, "bv": _bias,
    "wq": (-3,), "wk": (-3,), "wv": (-3,), "wi": (-2,), "wg": (-2,),
    "embedding": (-1,),
    "wo": (-3, -2),       # attention out-projection (..., H, hd, D)
    "mlp/wo": (-2,),      # MLP down-projection (..., F, D)
}


class WeightRule:
    """The rule a configuration draws its weights by: its module's
    ``WEIGHTS`` table (same form as ``BUILT_IN``) before the built-in
    one. ``origin`` names the module, for the error on a leaf that
    neither table places."""

    def __init__(self, table, origin: str):
        self.table = dict(table or {})
        self.origin = origin

    def place(self, path, shape):
        """The entry that draws the leaf at ``path`` (a key path)."""
        names = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        keys = ["/".join(names[-2:])] if len(names) > 1 else []
        keys.append(names[-1])
        for table in (self.table, BUILT_IN):
            for k in keys:
                if k in table:
                    return table[k]
        raise ValueError(
            f"no weight rule places leaf {'/'.join(names)} of shape "
            f"{tuple(shape)}: state it in WEIGHTS of {self.origin}, by "
            f"{keys[0]!r} or {names[-1]!r}")

    def check(self, shapes) -> None:
        """Raise, before anything is traced, on a leaf no rule places."""
        for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            self.place(path, s.shape)

    def draw(self, path, shape, key):
        how = self.place(path, shape)
        if callable(how):
            return how(shape, key)
        if isinstance(how, tuple):
            fan_in = math.prod(shape[a] for a in how)
            draw = jax.random.normal(key, shape, jnp.float32)
            return draw / math.sqrt(fan_in)
        return jnp.full(shape, how, jnp.float32)


# the weights' stream: one the token stream, which folds the step into the
# seed's key, never reaches
_WEIGHTS = 2 ** 32 - 1


def weights_key(seed: int) -> jax.Array:
    return jax.random.fold_in(jax.random.key(seed), _WEIGHTS)


def weight_leaves(shapes, key, rule: WeightRule) -> list:
    """The weights' leaves, in the order of ``jax.tree.leaves(shapes)``,
    from ``weights_key(seed)``, leaf i drawn by ``rule`` from
    ``fold_in(key, i)``; traceable. The program's init, the reference's
    x0 and the change norms all take their weights from here."""
    paths, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [rule.draw(p, s.shape, jax.random.fold_in(key, i))
            for i, (p, s) in enumerate(paths)]


def make_weights(shapes, seed: int, rule: WeightRule):
    """float32 weights shaped like ``shapes`` (a pytree of ShapeDtypeStruct
    in the program's parameter layout), in one jitted call."""
    treedef = jax.tree.structure(shapes)
    gen = jax.jit(lambda key: jax.tree.unflatten(
        treedef, weight_leaves(shapes, key, rule)))
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
