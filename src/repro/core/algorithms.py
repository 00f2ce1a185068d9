"""Decentralized stochastic algorithms (paper §5, Table 1).

All three algorithms operate on *stacked* pytrees: every leaf carries a
leading node dimension ``n`` and node i's model copy lives at index i.  The
same functions drive

* the host/single-process reference used by the paper-claims benchmarks
  (leaves are small dense arrays), and
* the distributed runtime (leaves are sharded over the mesh node axis and
  the einsum gossip lowers to cross-node collectives; see
  :mod:`repro.dist.steps`).

``grad_fn(x_stacked, key) -> g_stacked`` must return one stochastic-oracle
sample per node (Assumption 2); MC-DSGT performs its R-sample gradient
accumulation internally.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import compress, driver, engine

PyTree = Any
GradFn = Callable[[PyTree, jax.Array], PyTree]


# ---------------------------------------------------------------------------
# Gossip primitives on stacked pytrees
# ---------------------------------------------------------------------------

def mix(W: jax.Array, tree: PyTree) -> PyTree:
    """z_i = sum_j W[i, j] y_j on every leaf (partial-averaging protocol)."""
    def _m(x):
        return jnp.einsum("ij,j...->i...", W.astype(x.dtype), x)
    return jax.tree.map(_m, tree)


def multi_consensus(Ws: jax.Array, tree: PyTree, *, unroll: bool = False) -> PyTree:
    """Algorithm 2: apply W^{t1}, ..., W^{t2-1} in sequence.  ``Ws`` is the
    (R, n, n) stack for the window [t1, t2).  ``unroll`` replaces the scan
    with a Python loop (cost-probe lowering)."""
    if unroll:
        out = tree
        for r in range(Ws.shape[0]):
            out = mix(Ws[r], out)
        return out
    def body(z, W):
        return mix(W, z), None
    out, _ = jax.lax.scan(body, tree, Ws)
    return out


def sun_mix(center_mask: jax.Array, delta: float, tree: PyTree) -> PyTree:
    """Structured gossip for sun-shaped graphs (beyond-paper optimization).

    For W = I - (delta/n) L(S_{n,C}) the mixing decomposes into elementwise
    ops plus two node-axis sums:

        rim i:    z_i = y_i - (d/n)(k y_i)     + (d/n) * sum_{c in C} y_c
        center c: z_c = y_c - (d/n)(n y_c)     + (d/n) * sum_{all j} y_j

    Under GSPMD the two sums lower to all-reduces of ONE parameter volume
    each — O(2 V) on the wire instead of the O(n V) all-gather the dense
    einsum needs.  Exactly equal to mix(W, tree) for sun-shaped W.

    center_mask: (n,) float 0/1; delta = n(1-beta)/ceil(n(1-beta)).
    """
    n = center_mask.shape[0]
    k = jnp.sum(center_mask)

    def _m(x):
        m = center_mask.astype(x.dtype).reshape((n,) + (1,) * (x.ndim - 1))
        kx = k.astype(x.dtype)
        St = jnp.sum(x, axis=0, keepdims=True)
        Sc = jnp.sum(x * m, axis=0, keepdims=True)
        degp = kx + (n - kx) * m
        return x - (delta / n) * (degp * x) + (delta / n) * (Sc + m * (St - Sc))

    return jax.tree.map(_m, tree)


def sun_multi_consensus(center_masks: jax.Array, delta: float, tree: PyTree,
                        *, unroll: bool = True) -> PyTree:
    """Algorithm 2 specialised to a sun-shaped schedule: apply R structured
    mixings.  center_masks: (R, n)."""
    if unroll:
        out = tree
        for r in range(center_masks.shape[0]):
            out = sun_mix(center_masks[r], delta, out)
        return out

    def body(z, mask):
        return sun_mix(mask, delta, z), None

    out, _ = jax.lax.scan(body, tree, center_masks)
    return out


def one_peer_mix(peer: jax.Array, w_peer, tree: PyTree) -> PyTree:
    """Gossip for one-peer (perfect-matching) graphs — one-peer exponential
    [42], EquiRand/random matching [32, 39]: z_i = (1-w_i) y_i + w_i y_{peer(i)}.

    ``peer`` is the (n,) matching permutation (an involution); ``w_peer`` is
    a scalar or an (n,) per-node weight vector (symmetric pairs must share a
    weight for the matrix to stay doubly stochastic).  Under GSPMD the
    node-axis take lowers to a collective-permute — O(V) point-to-point
    instead of the dense einsum's O(nV) gather (beyond-paper).
    """
    def _m(x):
        w = jnp.asarray(w_peer, x.dtype)
        if w.ndim == 1:
            w = w.reshape((w.shape[0],) + (1,) * (x.ndim - 1))
        return (1.0 - w) * x + w * jnp.take(x, peer, axis=0)
    return jax.tree.map(_m, tree)


def complete_mix(avg_weight, tree: PyTree) -> PyTree:
    """Gossip for the complete graph with W = (1-a) I + a 11^T/n:
    z = (1-a) y + a ȳ.  The node-axis mean is ONE all-reduce of one
    parameter volume — O(V) on the wire, vs the dense einsum's O(nV)."""
    def _m(x):
        a = jnp.asarray(avg_weight, x.dtype)
        return (1.0 - a) * x + a * jnp.mean(x, axis=0, keepdims=True)
    return jax.tree.map(_m, tree)


def two_level_mix(B: jax.Array, pods: int, tree: PyTree) -> PyTree:
    """Hierarchical gossip for rounds that factor across pod boundaries,
    W = B ⊗ J_p with J_p = 11^T/p the intra-pod average and B the (m, m)
    doubly-stochastic inter-pod exchange (m = n/p pods of p nodes each,
    pod-major node order — matching the ``pod|data|model`` mesh layout).

    The lowering composes the two levels instead of the dense einsum:
    intra-pod mean (ONE all-reduce of one parameter volume per pod over
    the pod-local mesh axis under GSPMD), the tiny (m, m) inter-pod
    exchange on pod means (a matching/sun-style peer exchange when B is
    structured — m is small, so the einsum volume is m·V/p of the dense
    n·V), then broadcast back within each pod.  Exactly equal to
    ``mix(kron(B, J_p), tree)``."""
    def _m(x):
        n = x.shape[0]
        m = n // pods
        xp = x.reshape((m, pods) + x.shape[1:])
        pod_mean = jnp.mean(xp, axis=1)
        mixed = jnp.einsum("ij,j...->i...", B.astype(x.dtype), pod_mean)
        out = jnp.broadcast_to(mixed[:, None], xp.shape)
        return out.reshape(x.shape)
    return jax.tree.map(_m, tree)


def sparse_mix(src: jax.Array, dst: jax.Array, w: jax.Array,
               tree: PyTree) -> PyTree:
    """Edge-list gossip in Laplacian form (see :mod:`repro.sparse.plan`):
    ``z = x + scatter_{dst} w * (x[src] - x[dst])`` on every leaf — one
    gather + scatter-add of O(edges) rows instead of the dense einsum's
    O(n^2).  The diagonal is implied (row-stochastic by construction), so
    padded edges with ``w = 0`` are exactly inert and a dropped edge's
    weight lands back on the diagonal for free (the lazy channel repair).
    """
    def _m(x):
        wx = w.astype(x.dtype).reshape((-1,) + (1,) * (x.ndim - 1))
        contrib = wx * (jnp.take(x, src, axis=0) - jnp.take(x, dst, axis=0))
        return x.at[dst].add(contrib)
    return jax.tree.map(_m, tree)


def one_peer_mix_ppermute(perm: list, w_peer: float, tree: PyTree,
                          mesh, axis: str = "data") -> PyTree:
    """shard_map + lax.ppermute form of :func:`one_peer_mix` — the explicit
    point-to-point schedule (GSPMD lowers the take-based form to a full
    all-gather; this one provably emits collective-permute).

    perm: static list of (src, dst) node pairs (the matching, both
    directions).  Node axis must be fully sharded over ``axis``.
    """
    from jax.sharding import PartitionSpec as P

    def _mix_shard(x):
        y = jax.lax.ppermute(x, axis, perm)
        return (1.0 - w_peer) * x + w_peer * y

    def _m(x):
        spec = P(axis, *([None] * (x.ndim - 1)))
        return jax.shard_map(_mix_shard, mesh=mesh, in_specs=spec,
                             out_specs=spec)(x)

    return jax.tree.map(_m, tree)


# ---------------------------------------------------------------------------
# Planned gossip: consume a staged GossipPlan inside the jitted step
# ---------------------------------------------------------------------------

def make_plan_mixer(plan, *, mesh=None, axis: str = "data", mode: str | None = None,
                    dense_block=None):
    """Build ``mix_fn(tensors, t0, rounds, tree)`` applying rounds
    [t0, t0+rounds) of a :class:`repro.core.gossip.GossipPlan`.

    ``tensors`` is ``plan.tensors()`` staged on device **once** (the caller
    uploads it a single time and passes the same arrays every step — no
    per-step host transfer); ``t0`` is taken modulo the plan period.

    Two dispatch modes (default: ``plan.dispatch``, forced to ``static``
    when a mesh enables the ppermute matching path):

    * ``dynamic`` — requires a kind-uniform plan; ``t0`` may be a traced
      scalar, so ONE compilation serves every phase of the period (the
      round's parameters are gathered from the staged arrays by index);
    * ``static``  — ``t0`` must be concrete at trace time (pass it through
      ``jax.jit(..., static_argnums=...)``); each round dispatches on its
      statically-known kind, so ``empty`` rounds cost literally nothing and
      matchings may lower to an explicit ``lax.ppermute`` (``mesh`` given).
      The enclosing jit then specializes per start phase: a step consuming
      ``wps`` rounds compiles at most ``period / gcd(wps, period)`` distinct
      variants (5 for the built-in federated schedule), all within the
      first period.

    ``dense_block``: optional ``(Ws, tree) -> tree`` used for runs of
    consecutive dense rounds (e.g. the fused Pallas multi-consensus);
    defaults to the einsum scan.
    """
    P = plan.period
    kinds = plan.kinds
    has_matching = any(k == "matching" for k in kinds)
    if mode is None:
        mode = ("static" if plan.dispatch == "static"
                or (mesh is not None and has_matching) else "dynamic")
    if mode == "dynamic" and len(set(kinds)) != 1:
        raise ValueError("dynamic plan dispatch requires a kind-uniform plan; "
                         f"got {sorted(set(kinds))}")
    _dense_mc = dense_block or (lambda Ws, tr: multi_consensus(Ws, tr))

    def _apply_uniform(kind, tensors, idxs, tree):
        """Rounds ``idxs`` (all of one kind) as ONE lax.scan whose body is a
        single round: compile cost is O(1) in the window length (a Python
        loop of per-round gathers makes XLA's gather chains explode on long
        windows — one full period jitted at once is the worst case)."""
        if kind == "empty":
            return tree
        if kind == "dense":
            return _dense_mc(jnp.take(tensors["W"], idxs, axis=0), tree)
        if kind == "personalized":
            # base support only — a personalized rule's realized mix goes
            # through EngineOps.pmix (loss reweighting); plain mix() on a
            # personalized plan applies the row-stochastic prior as-is
            return _dense_mc(jnp.take(tensors["pW"], idxs, axis=0), tree)
        if kind == "two_level":
            xs = jnp.take(tensors["pod_B"], idxs, axis=0)
            body = lambda z, B: (two_level_mix(B, plan.pods, z), None)
        elif kind == "sun":
            xs = (jnp.take(tensors["center_mask"], idxs, axis=0),
                  jnp.take(tensors["delta"], idxs, axis=0))
            body = lambda z, md: (sun_mix(md[0], md[1], z), None)
        elif kind == "complete":
            xs = jnp.take(tensors["avg_w"], idxs, axis=0)
            body = lambda z, a: (complete_mix(a, z), None)
        elif kind == "sparse":
            xs = (jnp.take(tensors["esrc"], idxs, axis=0),
                  jnp.take(tensors["edst"], idxs, axis=0),
                  jnp.take(tensors["ew"], idxs, axis=0))
            body = lambda z, sdw: (sparse_mix(sdw[0], sdw[1], sdw[2], z), None)
        else:  # matching
            xs = (jnp.take(tensors["perm"], idxs, axis=0),
                  jnp.take(tensors["w_peer"], idxs, axis=0))
            body = lambda z, pw: (one_peer_mix(pw[0], pw[1], z), None)
        out, _ = jax.lax.scan(body, tree, xs)
        return out

    def _apply_static(tensors, t0, rounds, tree):
        t0 = int(t0)
        r = 0
        while r < rounds:  # group consecutive same-kind rounds
            kind = plan.rounds[(t0 + r) % P].kind
            stop = r
            while stop < rounds and plan.rounds[(t0 + stop) % P].kind == kind:
                stop += 1
            idx_list = [(t0 + q) % P for q in range(r, stop)]
            if kind == "matching" and mesh is not None:
                # explicit point-to-point schedule: perm is static here, so
                # each round lowers to a collective-permute
                for idx in idx_list:
                    rd = plan.rounds[idx]
                    if np.allclose(rd.w_peer, rd.w_peer[0]):
                        pairs = [(i, int(p)) for i, p in enumerate(rd.perm)]
                        tree = one_peer_mix_ppermute(
                            pairs, float(rd.w_peer[0]), tree, mesh, axis)
                    else:
                        tree = one_peer_mix(jnp.asarray(rd.perm),
                                            jnp.asarray(rd.w_peer), tree)
            elif kind != "empty":
                tree = _apply_uniform(kind, tensors, jnp.asarray(idx_list),
                                      tree)
            r = stop
        return tree

    def _apply_dynamic(tensors, t0, rounds, tree):
        idxs = (t0 + jnp.arange(rounds)) % P
        return _apply_uniform(kinds[0], tensors, idxs, tree)

    fn = _apply_static if mode == "static" else _apply_dynamic
    fn.dispatch = mode
    return fn


def node_mean(tree: PyTree) -> PyTree:
    return jax.tree.map(lambda x: jnp.mean(x, axis=0, keepdims=True), tree)


def broadcast_nodes(tree: PyTree, n: int) -> PyTree:
    """Stack n identical copies of an (unstacked) pytree."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


# Shared pytree arithmetic lives in the engine (single source); re-exported
# here for the runtimes and tests that import it from this module.
_axpy = engine._axpy
_accumulate = engine._accumulate


# ---------------------------------------------------------------------------
# Algorithm interfaces (thin adapters over repro.core.engine)
# ---------------------------------------------------------------------------

class AlgoState(NamedTuple):
    x: PyTree            # stacked model copies
    h: Optional[PyTree]  # gradient tracker (None for DSGD), x^{k-1} for D^2
    g_prev: Optional[PyTree]
    opt_state: Any
    k: jax.Array         # round counter
    res: Optional[tuple] = None  # compressed-gossip EF residuals (x, h)
    buf: Optional[tuple] = None  # stale-payload queues (x, h) when delay>0


@dataclasses.dataclass(frozen=True)
class DecentralizedAlgorithm:
    """A decentralized optimizer: ``weights`` passed to ``step`` is the
    (rounds, n, n) stack of gossip matrices this round consumes (rounds =
    ``weights_per_step``).  Built from an :class:`repro.core.engine`
    UpdateRule by :func:`from_rule` — the update arithmetic itself lives in
    the engine, shared with the distributed runtime."""

    name: str
    weights_per_step: int
    init: Callable[[PyTree], AlgoState]
    step: Callable[[AlgoState, GradFn, jax.Array, jax.Array], AlgoState]
    warm: Callable[[AlgoState, GradFn, jax.Array], AlgoState] = None
    rule: "engine.UpdateRule" = None
    local_opt: Any = None


def from_rule(rule: engine.UpdateRule, local_opt=None) -> DecentralizedAlgorithm:
    """Bind an UpdateRule to the host reference runtime: the stacked-einsum
    multi-consensus mixer and a ``grad_fn(x, key)`` oracle closure."""
    if local_opt is not None and not rule.supports_local_opt:
        raise ValueError(f"algo {rule.name!r} does not support a local "
                         "optimizer hook")

    def _ops(grad_fn, weights, key):
        cmix = None
        if rule.compression is not None:
            cmix = compress.make_compressed_mixer(
                lambda idx, m: mix(weights[idx], m), rule.compression)
        grad = lambda x: (None, engine._accumulate(grad_fn, x, key, rule.R))
        pmix = None
        if rule.personalized:
            # personalized oracle contract: grad_fn(x, key) -> (losses, g)
            # with losses the per-node (n,) loss vector of the sample — the
            # similarity signal pmix reweights the base rows with in-jit.
            grad = lambda x: grad_fn(x, key)
            pmix = lambda off, r, tree, losses: multi_consensus(
                engine.personalized_weights(weights[off:off + r], losses,
                                            rule.tau), tree)
        return engine.EngineOps(
            mix=lambda off, r, tree: multi_consensus(
                weights[off:off + r], tree),
            grad=grad,
            local_update=(local_opt.update if local_opt
                          else (lambda g, s: (g, s))),
            cast_aux=lambda tree: tree,
            cmix=cmix,
            pmix=pmix)

    def _to_engine(s: AlgoState) -> engine.EngineState:
        return engine.EngineState(s.x, s.h, s.g_prev, s.opt_state, s.k,
                                  s.res, s.buf)

    def _to_algo(s: engine.EngineState) -> AlgoState:
        return AlgoState(s.x, s.h, s.g_prev, s.opt, s.k, s.res, s.buf)

    def init(x0: PyTree) -> AlgoState:
        return _to_algo(engine.init_state(
            rule, x0, opt_init=local_opt.init if local_opt else None))

    def step(state: AlgoState, grad_fn: GradFn, weights: jax.Array,
             key: jax.Array, obs: tuple = ()) -> AlgoState:
        """One round; with ``obs`` metric names (repro.obs), returns
        ``(state, obs_dict)`` — the engine's in-jit scalars."""
        es, aux = engine.step(rule, _to_engine(state),
                              _ops(grad_fn, weights, key), obs=obs)
        if obs:
            return _to_algo(es), aux[1]
        return _to_algo(es)

    def warm(state: AlgoState, grad_fn: GradFn, key: jax.Array) -> AlgoState:
        return _to_algo(engine.warm_start(rule, _to_engine(state),
                                          _ops(grad_fn, None, key)))

    return DecentralizedAlgorithm(rule.name, rule.weights_per_step, init,
                                  step, warm, rule, local_opt)


def plan_step(algo: DecentralizedAlgorithm, plan, *, mesh=None,
              axis: str = "data"):
    """Bind ``algo``'s update rule to a staged :class:`repro.core.gossip.
    GossipPlan` — the host-runtime analogue of ``dist.steps``'
    ``gossip_impl='auto'``.  Returns ``step(state, grad_fn, tensors, t,
    key)`` where ``tensors`` is the plan staged on device once
    (:func:`repro.core.driver.stage_plan`) and ``t`` the start round
    (concrete at trace time when ``step.dispatch == 'static'``).  Realized
    post-fault schedules (:mod:`repro.sim`) ride this path too: degraded
    matchings take the one-peer lowering and fully dropped (``empty``)
    rounds cost nothing."""
    rule = algo.rule
    if rule is None:
        raise ValueError("plan_step requires an engine-rule algorithm "
                         "(built via from_rule)")
    # Edge-list plans (repro.sparse.SparseGossipPlan) carry their own mixer
    # factory with the same mix_fn contract — duck-typed so the core stays
    # import-free of the sparse subsystem.
    if hasattr(plan, "make_mixer"):
        mixer = plan.make_mixer(mesh=mesh, axis=axis)
    else:
        mixer = make_plan_mixer(plan, mesh=mesh, axis=axis)
    local_update = (algo.local_opt.update if algo.local_opt is not None
                    else (lambda g, s: (g, s)))

    def pstep(state: AlgoState, grad_fn: GradFn, tensors, t,
              key: jax.Array, obs: tuple = ()) -> AlgoState:
        cmix = None
        if rule.compression is not None:
            cmix = compress.make_compressed_mixer(
                lambda idx, m: mixer(tensors, t + idx, 1, m),
                rule.compression)
        grad = lambda x: (None, engine._accumulate(grad_fn, x, key, rule.R))
        pmix = None
        if rule.personalized:
            # staged per-node base rows ("pW", never a dense fallback) are
            # reweighted in-jit by this step's per-node losses; same oracle
            # contract as from_rule: grad_fn(x, key) -> (losses, g)
            grad = lambda x: grad_fn(x, key)

            def pmix(off, r, tree, losses):
                idxs = (t + off + jnp.arange(r)) % plan.period
                Ws = engine.personalized_weights(
                    jnp.take(tensors["pW"], idxs, axis=0), losses, rule.tau)
                return multi_consensus(Ws, tree)
        ops = engine.EngineOps(
            mix=lambda off, r, tree: mixer(tensors, t + off, r, tree),
            grad=grad,
            local_update=local_update,
            cast_aux=lambda tree: tree,
            cmix=cmix,
            pmix=pmix)
        es, aux = engine.step(rule, engine.EngineState(
            state.x, state.h, state.g_prev, state.opt_state, state.k,
            state.res, state.buf), ops, obs=obs)
        new = AlgoState(es.x, es.h, es.g_prev, es.opt, es.k, es.res, es.buf)
        return (new, aux[1]) if obs else new

    pstep.dispatch = mixer.dispatch
    return pstep


# -- The paper's rules + the federated/local-update family, one line each. --

def dsgd(gamma: float, local_opt=None) -> DecentralizedAlgorithm:
    """DSGD [12]: x^{k+1} = W^k (x^k - gamma * g^k)."""
    return from_rule(engine.make_rule("dsgd", gamma), local_opt)


def dsgt(gamma: float) -> DecentralizedAlgorithm:
    """DSGT [40]: x^{k+1} = W (x^k - gamma h^k);
    h^{k+1} = W (h^k + g^{k+1} - g^k).  Two gossip rounds per step."""
    return from_rule(engine.make_rule("dsgt", gamma))


def mc_dsgt(gamma: float, R: int) -> DecentralizedAlgorithm:
    """Multi-Consensus DSGT (Algorithm 1): R-sample gradient accumulation
    and R gossip rounds per consensus phase; ``weights`` is the (2R, n, n)
    stack [W^{2kR}, ..., W^{(2k+2)R - 1}] (first R mix x, last R mix h)."""
    return from_rule(engine.make_rule("mc_dsgt", gamma, R=R))


def d2(gamma: float) -> DecentralizedAlgorithm:
    """D^2 [35]: x^{k+1} = W(2 x^k - x^{k-1} - gamma (g^k - g^{k-1})).
    Requires symmetric PSD W (the Theorem 3 matrices qualify)."""
    return from_rule(engine.make_rule("d2", gamma))


def local_sgd(gamma: float, local_opt=None) -> DecentralizedAlgorithm:
    """Local SGD / FedAvg as an update rule: x^{k+1} = W^k x^k - gamma g^k
    with the oracle queried at the mixed iterate.  Over a federated
    schedule, ``empty`` rounds make this a pure local step and the
    periodic ``complete`` round is the global average (paper §1)."""
    return from_rule(engine.make_rule("local_sgd", gamma), local_opt)


def personalized(gamma: float, tau: float = 4.0,
                 local_opt=None) -> DecentralizedAlgorithm:
    """Dada-style personalized neighbor averaging: x ← P(ℓ)(x − γ g) with
    P(ℓ) the loss-proximity reweighting of the round's support
    (:func:`repro.core.engine.personalized_weights`).  The fleet converges
    to n personalized models, not one consensus model; ``grad_fn`` must
    return ``(per-node losses, grads)``."""
    return from_rule(engine.make_rule("personalized", gamma, tau=tau),
                     local_opt)


def gt_local(gamma: float, local_opt=None) -> DecentralizedAlgorithm:
    """Gradient tracking with local updates (DIGing-style placement):
    x^{k+1} = W^k x^k - gamma h^k;  h^{k+1} = W^k h^k + g^{k+1} - g^k.
    x and h share ONE gossip round per step and the tracker correction
    stays local, so the tracker keeps tracking through empty (local-only)
    rounds of a federated schedule."""
    return from_rule(engine.make_rule("gt_local", gamma), local_opt)


def warm_start(algo: DecentralizedAlgorithm, state: AlgoState,
               grad_fn: GradFn, key: jax.Array) -> AlgoState:
    """Tracker/correction initialization (Algorithm 1's h^0 for the
    tracking rules; x^{-1}/g^{-1} for D^2) — delegates to the engine."""
    return algo.warm(state, grad_fn, key)


# ---------------------------------------------------------------------------
# Driver (delegates to the unified repro.core.driver loop)
# ---------------------------------------------------------------------------

def run(algo: DecentralizedAlgorithm, x0: PyTree, grad_fn: GradFn,
        weight_schedule, num_steps: int, key: jax.Array,
        eval_fn: Optional[Callable[[PyTree], Any]] = None,
        eval_every: int = 1, gossip_impl: str = "dense", telemetry=None,
        obs: tuple = (), tracer=None):
    """Host-side training loop over a :class:`repro.core.gossip.WeightSchedule`.

    The schedule is staged on device ONCE up front — one period (or, for
    aperiodic schedules, the whole run's window) of matrices — and the
    jitted step gathers its ``weights_per_step`` rounds from the staged
    stack by index: no per-step host ``stacked()`` + transfer.  The
    staging, loop, and history recording are the shared
    :mod:`repro.core.driver` (same code path as the distributed CLI).

    Returns (final_state, history) where history records ``eval_fn`` of the
    node-mean model x-bar every ``eval_every`` rounds, keyed by the total
    gossip/oracle budget T = k * weights_per_step consumed so far (the
    paper's x-axis in Figure 2).
    """
    return driver.run_algorithm(algo, x0, grad_fn, weight_schedule,
                                num_steps, key, eval_fn=eval_fn,
                                eval_every=eval_every,
                                gossip_impl=gossip_impl, telemetry=telemetry,
                                obs=obs, tracer=tracer)
