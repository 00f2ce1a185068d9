"""Jitted distributed steps over a stacked node state.

This module is a thin ADAPTER: the update arithmetic for every algorithm
(mc_dsgt / dsgt / dsgd / d2 / local_sgd / gt_local) lives once in
:mod:`repro.core.engine`; here we only bind the engine's :class:`EngineOps`
to the distributed substrate — the mesh/plan gossip mixers, the clipped
R-microbatch loss/grad, and the bf16 tracker cast.

``make_train_step`` builds the three callables the drivers and tests consume:

* ``init_state(key, n, dtype)`` — n identical model copies (leading node
  axis on every leaf) plus zeroed tracker state;
* ``warm_start(state, batch)`` — the rule's tracker init (Algorithm 1's
  h^0 = (1/n) sum_i g~_i^0 replicated for the MC-DSGT family);
* ``step(state, batch, weights) -> (state, {"loss": ...})`` — one paper
  round.  ``batch`` leaves are (n, R, b, ...) so the R gradient-accumulation
  microbatches are Assumption 2's independent oracle draws; ``weights`` is
  the (2R, n, n) gossip stack (or (2R, n) center masks for the structured
  sun path).

The gossip mixing runs through :func:`repro.core.algorithms.multi_consensus`
(an einsum over the node axis — under GSPMD with the node axis sharded this
lowers to cross-node collectives), through the structured sun rewrite,
through the fused Pallas kernel (``gossip_impl="pallas"``) which applies all
R rounds in one VMEM-resident pass, or — ``gossip_impl="auto"`` — through a
:class:`repro.core.gossip.GossipPlan` that dispatches every round to its
cheapest lowering (sun / one-peer matching / complete-graph mean / dense)
from plan tensors staged on device once.

Tracker state (h, g_prev) can be held in a lower precision via ``aux_dtype``
(H2: bf16 trackers halve the steady-state HBM of the tracker copies);
updates are computed in the gradient dtype and cast on store.

Unlike the host-side reference in :mod:`repro.core.algorithms` (which stays
letter-faithful to Algorithm 1), the runtime clips each node's accumulated
oracle sample to a global norm (``clip``, default 1.0) before it enters the
tracker — the standard LM-training stabilizer.  Raw per-sequence gradient
norms on the transformer configs sit at 5-12, so the paper-pure update at
the test stepsizes is past the edge of stability; the tracker then simply
tracks the mean *clipped* gradient.  ``clip=None`` restores the pure update.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core import algorithms as alg, compress, engine
from . import collectives as coll

PyTree = Any


class TrainState(NamedTuple):
    x: PyTree                  # stacked model copies (n leading)
    h: PyTree                  # gradient tracker (zeros until warm_start)
    g_prev: PyTree             # previous accumulated oracle sample
    step: jax.Array            # round counter
    opt: Any = None            # local-optimizer state (framework extension)
    res: Any = None            # compressed-gossip EF residuals (x, h)
    buf: Any = None            # stale-payload queues (x, h) when delay>0


def make_train_step(model, cfg, *, algo: str = "mc_dsgt", gamma: float,
                    R: int = 1, aux_dtype=None, gossip_impl: str = "dense",
                    sun_delta: Optional[float] = None, local_opt=None,
                    clip: Optional[float] = 1.0, unroll: bool = False,
                    pallas_block_d: int = 1024, pallas_interpret="auto",
                    plan=None, mesh=None, gossip_axis: str = "data",
                    auto_dense: str = "einsum", obs: tuple = (),
                    compression: Optional[compress.CompressionConfig] = None,
                    delay: int = 0, comm_interval: int = 1,
                    tau: float = 4.0):
    """Build (init_state, warm_start, step) for one decentralized algorithm.

    gossip_impl: 'dense' (einsum multi-consensus), 'sun' (structured
    sun-graph rewrite; ``weights`` becomes (2R, n) center masks and
    ``sun_delta`` must be given), 'pallas' (fused gossip_mix kernel;
    ``pallas_interpret`` follows :func:`repro.kernels.ops.resolve_interpret`
    — "auto" interprets off-TPU), or 'auto' (per-round structured dispatch
    from a :class:`repro.core.gossip.GossipPlan`; ``plan`` must be given).

    ``compression`` (a :class:`repro.core.compress.CompressionConfig`)
    turns every gossip payload into its quantized error-feedback form; the
    'pallas' impl routes it through the fused quantize->mix->dequantize
    kernel, every other impl wraps its per-round mixer via
    :func:`repro.core.compress.make_compressed_mixer` — bit-identical
    semantics either way.

    For 'dense'/'sun'/'pallas' the step is ``step(state, batch, weights)``
    with ``weights`` the per-step gossip stack.  For 'auto' it is
    ``step(state, batch, plan_tensors, t)``: ``plan_tensors`` is
    ``plan.tensors()`` staged on device ONCE, ``t`` the start round modulo
    the plan period — a Python int when ``step.gossip_dispatch == 'static'``
    (jit it with ``static_argnums=3``), a traced scalar otherwise.
    ``mesh``/``gossip_axis`` enable the explicit ppermute matching lowering;
    ``auto_dense='pallas'`` routes runs of dense rounds through the fused
    Pallas kernel instead of the einsum scan.

    ``obs`` names in-jit observability scalars (repro.obs /
    :data:`repro.core.engine.OBS_METRICS`): when non-empty the step's
    output dict gains an ``"obs"`` entry of device scalars, computed by
    the shared engine — no extra host syncs.

    ``delay`` > 0 enables the stale-window double buffer (overlapped
    gossip): the step mixes the payload from ``delay`` steps ago and folds
    the correction into the fresh payload, so the collectives carry no
    data dependence on the current grad and XLA may run them concurrently
    (see :class:`repro.core.engine.UpdateRule`).  ``comm_interval`` mixes
    every k steps (identity mix in between).  Both default to today's
    synchronous path, bit-exact.
    """
    rule = engine.make_rule(algo, gamma=gamma,
                            R=(1 if algo == "d2" else R),
                            compression=compression, delay=delay,
                            comm_interval=comm_interval, tau=tau)
    if gossip_impl not in ("dense", "sun", "pallas", "auto"):
        raise ValueError(f"unknown gossip_impl {gossip_impl!r}")
    if rule.personalized and gossip_impl not in ("dense", "auto"):
        raise ValueError("personalized weights are reweighted per step in "
                         "full precision; use gossip_impl 'dense' or 'auto'")
    if gossip_impl == "sun" and sun_delta is None:
        raise ValueError("gossip_impl='sun' requires sun_delta")
    if gossip_impl == "auto" and plan is None:
        raise ValueError("gossip_impl='auto' requires plan=GossipPlan")
    if local_opt is not None and not rule.supports_local_opt:
        raise ValueError(f"algo={algo!r} does not support a local-optimizer "
                         "hook")

    def _mc(Ws, tree):
        if gossip_impl == "sun":
            return alg.sun_multi_consensus(Ws, sun_delta, tree, unroll=True)
        if gossip_impl == "pallas":
            return coll.fused_multi_consensus(
                Ws, tree, block_d=pallas_block_d, interpret=pallas_interpret)
        return alg.multi_consensus(Ws, tree, unroll=unroll)

    if gossip_impl == "auto":
        dense_block = None
        if auto_dense == "pallas":
            dense_block = lambda Ws, tr: coll.fused_multi_consensus(
                Ws, tr, block_d=pallas_block_d, interpret=pallas_interpret)
        _plan_mix = alg.make_plan_mixer(plan, mesh=mesh, axis=gossip_axis,
                                        dense_block=dense_block)

    def _mix_rounds(gossip, t, offset, rounds, tree):
        """Rounds [t+offset, t+offset+rounds) — from the staged plan under
        'auto', else the per-step ``weights`` stack slice."""
        if gossip_impl == "auto":
            return _plan_mix(gossip, t + offset, rounds, tree)
        return _mc(gossip[offset:offset + rounds], tree)

    def _clip(g):
        if clip is None:
            return g
        nrm = jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2)
                           for l in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, clip / (nrm + 1e-12))
        return jax.tree.map(lambda l: l * scale.astype(l.dtype), g)

    def _grads(x_stacked, batch):
        """Per-node R-sample gradient accumulation (clipped); returns
        (mean loss, stacked grads) — or (per-node losses, stacked grads)
        for personalized rules, whose pmix needs the (n,) loss vector as
        its similarity signal (the ``core`` wrapper re-means it for the
        step's "loss" output)."""
        def per_node(params, node_batch):  # node_batch leaves: (R, b, ...)
            vg = jax.value_and_grad(model.train_loss)
            if R == 1:
                loss, g = vg(params, jax.tree.map(lambda t: t[0], node_batch))
                return loss, _clip(g)
            if unroll:
                loss = jnp.zeros((), jnp.float32)
                g = jax.tree.map(jnp.zeros_like, params)
                for r in range(R):
                    micro = jax.tree.map(lambda t: t[r], node_batch)
                    l, gr = vg(params, micro)
                    loss = loss + l
                    g = jax.tree.map(jnp.add, g, gr)
            else:
                def body(carry, micro):
                    l, gr = vg(params, micro)
                    return (carry[0] + l,
                            jax.tree.map(jnp.add, carry[1], gr)), None

                zero = (jnp.zeros((), jnp.float32),
                        jax.tree.map(jnp.zeros_like, params))
                (loss, g), _ = jax.lax.scan(body, zero, node_batch)
            return loss / R, _clip(jax.tree.map(lambda t: t / R, g))

        losses, grads = jax.vmap(per_node)(x_stacked, batch)
        if rule.personalized:
            return losses, grads
        return jnp.mean(losses), grads

    def init_state(key, n: int, dtype) -> TrainState:
        # every leaf gets a buffer of its own: warm_start and the driver's
        # step donate the state, and one buffer cannot be donated twice
        params = model.init(key, dtype)
        x = alg.broadcast_nodes(params, n)
        aux = lambda: jax.tree.map(
            lambda l: jnp.zeros(l.shape, aux_dtype or l.dtype), x)
        opt = local_opt.init(x) if local_opt is not None else None
        res = (compress.init_residual(x, rule.uses_tracker, dtype=aux_dtype)
               if compression is not None else None)
        buf = None
        if rule.delay:
            # Stale-payload FIFO queues, mirroring engine.init_state: the x
            # stream seeds with x⁰ (zero correction for the first ``delay``
            # steps under broadcast-identical init); the tracker stream is
            # re-seeded with h⁰ by warm_start.
            hq = (tuple(aux() for _ in range(rule.delay))
                  if rule.uses_tracker else None)
            buf = (tuple(jax.tree.map(jnp.copy, x)
                         for _ in range(rule.delay)), hq)
        return TrainState(x=x, h=aux(), g_prev=aux(),
                          step=jnp.zeros((), jnp.int32),
                          opt=opt, res=res, buf=buf)

    # Bind the engine's abstract ops to this runtime: the selected gossip
    # mixer, the clipped R-microbatch oracle, the local-optimizer hook and
    # the bf16 tracker cast.  The update arithmetic itself is
    # engine.step(rule, ...) — shared verbatim with the host reference.
    def _ops(batch, gossip, t):
        cmix = None
        if compression is not None:
            if gossip_impl == "pallas":
                # Fully fused: quantize -> mix -> dequantize -> residual in
                # one VMEM-resident Pallas pass over the whole window.
                cmix = lambda off, r, tree, res, on: \
                    coll.fused_quantized_consensus(
                        gossip[off:off + r], tree, res, cfg=compression,
                        on=on, block_d=pallas_block_d,
                        interpret=pallas_interpret)
            else:
                cmix = compress.make_compressed_mixer(
                    lambda idx, m: _mix_rounds(gossip, t, idx, 1, m),
                    compression)
        pmix = None
        if rule.personalized:
            # In-jit loss-proximity reweighting of the round window's base
            # weights: the staged per-node rows ("pW" — never a dense
            # fallback) under 'auto', the per-step stack slice under
            # 'dense'.  ``losses`` is _grads' per-node vector.
            if gossip_impl == "auto":
                def pmix(off, r, tree, losses):
                    idxs = (t + off + jnp.arange(r)) % plan.period
                    Ws = engine.personalized_weights(
                        jnp.take(gossip["pW"], idxs, axis=0), losses, rule.tau)
                    return alg.multi_consensus(Ws, tree, unroll=unroll)
            else:
                def pmix(off, r, tree, losses):
                    Ws = engine.personalized_weights(
                        gossip[off:off + r], losses, rule.tau)
                    return alg.multi_consensus(Ws, tree, unroll=unroll)
        return engine.EngineOps(
            mix=lambda off, r, tree: _mix_rounds(gossip, t, off, r, tree),
            grad=lambda x: _grads(x, batch),  # metrics = scalar mean loss
            local_update=(local_opt.update if local_opt is not None
                          else (lambda g, s: (g, s))),
            cast_aux=lambda tree: coll.tree_cast(tree, aux_dtype),
            cmix=cmix,
            pmix=pmix)

    def _to_engine(s: TrainState) -> engine.EngineState:
        return engine.EngineState(s.x, s.h, s.g_prev, s.opt, s.step,
                                  res=s.res, buf=s.buf)

    def _to_train(s: engine.EngineState) -> TrainState:
        return TrainState(x=s.x, h=s.h, g_prev=s.g_prev, step=s.k, opt=s.opt,
                          res=s.res, buf=s.buf)

    def warm_start(state: TrainState, batch) -> TrainState:
        ops = _ops(batch, None, 0)  # warm start never gossips
        return _to_train(engine.warm_start(rule, _to_engine(state), ops))

    # personalized _grads returns the per-node loss vector (pmix's
    # similarity signal); the step's "loss" output stays the scalar mean
    _loss_out = jnp.mean if rule.personalized else (lambda m: m)

    def core(state: TrainState, batch, gossip, t):
        es, aux = engine.step(rule, _to_engine(state),
                              _ops(batch, gossip, t), obs=obs)
        if obs:
            loss, scalars = aux
            return _to_train(es), {"loss": _loss_out(loss), "obs": scalars}
        return _to_train(es), {"loss": _loss_out(aux)}
    if gossip_impl == "auto":
        step = core
        step.gossip_dispatch = _plan_mix.dispatch
    else:
        def step(state: TrainState, batch, weights):
            return core(state, batch, weights, 0)
    # warm_start donates its input state (always a fresh init_state): at
    # full width, input and output state would not both fit one device.
    # keep_unused: h/g_prev are overwritten, not read, and an unused input
    # would otherwise be pruned and stay live instead of being donated
    return (init_state,
            jax.jit(warm_start, donate_argnums=0, keep_unused=True), step)


def make_prefill_step(model, cfg):
    """(params, batch, cache) -> (last-position logits, filled cache)."""
    del cfg

    def step(params, batch, cache):
        return model.prefill(params, batch, cache)

    return step


def make_serve_step(model, cfg):
    """(params, token, cache, pos) -> (logits, cache) for one decode step."""
    del cfg

    def step(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos)

    return step
