"""Plain reference of the timed path's first steps, and the comparison
that decides ``correct``.

The reference runs MC-DSGT (Algorithm 1, as the distributed runtime
states it) from the same weights, batches and gossip weights as the run:

* warm start: g0_i = clip(mean_r grad f_i(x0; batch0_ir)), h0 = mean_i g0_i,
  g_prev = g0;
* step k: x <- W_{t+R-1} .. W_t (x - gamma h); g_i = clip(mean_r grad f_i(x_i;
  batch_{k+1,ir})); h <- W_{t+2R-1} .. W_{t+R} (h + g - g_prev); g_prev <- g,
  with t = 2Rk and every clip to global norm 1 per node.

It records what the comparison reads: each step's mean loss, the
per-node, per-leaf norms of g_prev after the first step (the gradient as
the optimizer gets it) and of h less the nodes' mean h after it (what the
gossip weights did to the nodes' differing gradients: h_0 is the same at
every node), and the norms of x_3 - x_0. Every node's x, h and
g_prev is a list of leaves of its own on the device, and one node's
gradient is taken at a time, with no copy of a node sliced out of a
stacked array: qwen1.5-0.5b's two nodes (11.1 GB of x, h, g_prev) then
fit one chip with one gradient beside them.
"""

from __future__ import annotations

import contextlib
import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3
CLIP = 1.0


def repair(w: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """A lost link's weight stays with the receiver: off-diagonal weight
    where ``keep`` is False moves to the diagonal of its row."""
    off = ~np.eye(len(w), dtype=bool)
    out = np.where(keep | ~off, w, 0.0)
    out[~off] += np.where(~keep & off, w, 0.0).sum(axis=1)
    return out


def check_doubly_stochastic(w: np.ndarray) -> None:
    """The guarantee the schedule states (the paper's Assumption 3)."""
    ok = (np.all(w >= -1e-12) and np.allclose(w, w.T, atol=1e-9)
          and np.allclose(w.sum(1), 1.0, atol=1e-9))
    if not ok:
        raise ValueError(f"gossip weights are not symmetric doubly stochastic:"
                         f"\n{w}")


def run(loss_fn, cfg, x0, batch_at, weights_at, *, nodes, R, gamma,
        dtype=jnp.float32, precision="highest"):
    """The reference's readings: ``{"loss": [3], "grad": (leaves, n),
    "spread": (leaves, n),
    "change": (leaves, n)}``. ``x0`` is one float32 copy of the weights,
    ``batch_at(k)`` the run's batch ``(n, R, b, ...)`` of step k and
    ``weights_at(t)`` the (n, n) gossip weights of round t."""
    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    with ctx:
        return _run(loss_fn, cfg, x0, batch_at, weights_at, nodes=nodes,
                    R=R, gamma=gamma, dtype=dtype)


def _run(loss_fn, cfg, x0, batch_at, weights_at, *, nodes, R, gamma, dtype):
    leaves0, treedef = jax.tree.flatten(x0)
    tree = lambda ls: jax.tree.unflatten(treedef, ls)

    @jax.jit
    def node_grad(leaves, micro):
        """Clipped R-sample gradient and mean loss of one node."""
        vg = jax.value_and_grad(lambda q, b: loss_fn(q, b, cfg))
        tot_l, tot_g = 0.0, None
        for r in range(R):
            l, g = vg(tree(leaves), jax.tree.map(lambda b: b[r].astype(
                dtype if b.dtype.kind == "f" else b.dtype), micro))
            tot_l = tot_l + l.astype(jnp.float32)
            tot_g = g if tot_g is None else jax.tree.map(jnp.add, tot_g, g)
        g = [t / R for t in jax.tree.leaves(tot_g)]
        nrm = jnp.sqrt(sum(jnp.sum(jnp.square(t.astype(jnp.float32)))
                           for t in g))
        scale = jnp.minimum(1.0, CLIP / (nrm + 1e-12))
        return tot_l / R, [t * scale.astype(t.dtype) for t in g]

    def mix(state, ws):
        """Each round: node i's leaf j <- sum_k w_ik (node k's leaf j)."""
        for w in ws:
            w = jnp.asarray(w, dtype)
            for j in range(len(leaves0)):
                new = _mix_leaf(w, [state[i][j] for i in range(nodes)])
                for i in range(nodes):
                    state[i][j] = new[i]
        return state

    # per node, a list of leaves: x, h and g_prev on the device
    # a buffer of its own per node and leaf: the updates donate them
    own = lambda l: jnp.array(l, dtype=dtype, copy=True)
    x = [[own(l) for l in leaves0] for _ in range(nodes)]
    batch = batch_at(0)
    g = [node_grad(x[i], jax.tree.map(lambda b: b[i], batch))[1]
         for i in range(nodes)]
    mean = [sum(g[i][j] for i in range(nodes)) / nodes
            for j in range(len(leaves0))]
    h = [[own(m) for m in mean] for _ in range(nodes)]
    del mean
    g_prev = g
    out = {"loss": []}
    for k in range(STEPS):
        t = 2 * R * k
        x = [[_axpy(u, v, -gamma) for u, v in zip(x[i], h[i])]
             for i in range(nodes)]
        x = mix(x, [weights_at(t + r) for r in range(R)])
        batch = batch_at(k + 1)
        losses = []
        for i in range(nodes):
            loss, gi = node_grad(x[i], jax.tree.map(lambda b: b[i], batch))
            losses.append(float(loss))
            h[i] = [_tracker(a, b, c) for a, b, c in zip(h[i], gi, g_prev[i])]
            g_prev[i] = gi
        h = mix(h, [weights_at(t + R + r) for r in range(R)])
        out["loss"].append(float(np.mean(losses)))
        if k == 0:
            out["grad"] = np.stack([np.asarray(_norms(
                [g_prev[i][j] for i in range(nodes)]), np.float64)
                for j in range(len(leaves0))])
            out["spread"] = np.stack([np.asarray(_spread(
                [h[i][j] for i in range(nodes)]), np.float64)
                for j in range(len(leaves0))])
    del h, g_prev
    out["change"] = np.stack([np.asarray(_change(
        [x[i][j] for i in range(nodes)], leaves0[j]), np.float64)
        for j in range(len(leaves0))])
    return out


@functools.partial(jax.jit, donate_argnums=0)
def _axpy(u, v, a):
    return u + a * v


@functools.partial(jax.jit, donate_argnums=0)
def _tracker(h, g, g_prev):
    return h + g - g_prev


@functools.partial(jax.jit, donate_argnums=1)
def _mix_leaf(w, ls):
    return [sum(w[i, k] * ls[k] for k in range(len(ls)))
            for i in range(len(ls))]


@jax.jit
def _norms(ls):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in ls])


@jax.jit
def _spread(ls):
    mean = sum(l.astype(jnp.float32) for l in ls) / len(ls)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32) - mean)))
                      for l in ls])


@jax.jit
def _change(ls, l0):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        l.astype(jnp.float32) - l0.astype(jnp.float32)))) for l in ls])


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

def leaf_gap(got: np.ndarray, ref: np.ndarray,
             keep: np.ndarray | None = None) -> float:
    """Worst leaf: the gap between the two norms of a (leaf, node) entry,
    over the larger of the reference's norm of that entry and of the
    median entry."""
    if keep is not None:
        got, ref = got[keep], ref[keep]
    med = statistics.median(ref.ravel().tolist())
    den = np.maximum(ref, med)
    with np.errstate(invalid="ignore", divide="ignore"):
        return float(np.max(np.abs(got - ref) / den))


def readings(got: dict, ref: dict) -> dict:
    """The numbers compared: worst relative loss gap over the steps, worst
    leaf of the first gradient, of the trackers' spread about their mean
    after the first step, and of the change over the steps.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's (nought to rounding, as a key bias under softmax) are left out
    of the change."""
    lg = np.asarray(got["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    gmax = ref["grad"].max(axis=1)
    keep = gmax >= 1e-3 * statistics.median(ref["grad"].ravel().tolist())
    out = {"loss_gap": float(np.max(np.abs(lg - lr) / np.abs(lr))),
           "grad_gap": leaf_gap(got["grad"], ref["grad"]),
           "change_gap": leaf_gap(got["change"], ref["change"], keep)}
    if "spread" in got:
        out["spread_gap"] = leaf_gap(got["spread"], ref["spread"])
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}
