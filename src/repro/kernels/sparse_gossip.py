"""Sparse gossip segment-sum Pallas TPU kernel.

Computes ``delta[s] = sum_{e: seg[e] == s} w[e] * (xs[e] - xd[e])`` — the
per-receiver update of one edge-list gossip round (Laplacian form, see
:mod:`repro.sparse.plan`).  TPUs have no native scatter-add in VMEM, so
the segment sum is expressed as an MXU matmul: each edge chunk builds a
(S, be) one-hot matrix from its segment ids (``broadcasted_iota`` against
the seg block — TPU requires >= 2-D iota) and multiplies it into the
(be, bd) weighted edge differences, accumulating (S, bd) output tiles
across edge chunks.  S is the *compacted* receiver count (at most the
sampled cohort size k, not n), so the output tile stays in VMEM while
edges stream through.

Padded edges carry ``w = 0`` and contribute exactly zero, so callers may
pad E freely to the block size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret


def _kernel(seg_ref, w_ref, xs_ref, xd_ref, o_ref, *, num_segments):
    e = pl.program_id(1)
    seg = seg_ref[0, :]                       # (be,) int32
    w = w_ref[0, :].astype(jnp.float32)       # (be,)
    xs = xs_ref[...].astype(jnp.float32)      # (be, bd)
    xd = xd_ref[...].astype(jnp.float32)
    contrib = w[:, None] * (xs - xd)          # (be, bd)
    ids = jax.lax.broadcasted_iota(jnp.int32, (num_segments, seg.shape[0]), 0)
    onehot = (ids == seg[None, :]).astype(jnp.float32)  # (S, be)
    # HIGHEST: at the MXU's default precision contrib would round through
    # bf16 (see gossip_matmul._kernel)
    acc = jax.lax.dot_general(onehot, contrib, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(e != 0)
    def _accum():
        o_ref[...] += acc


def sparse_segment_mix(seg, w, xs, xd, *, num_segments, block_e=512,
                       block_d=512, interpret="auto"):
    """seg, w: (E,); xs, xd: (E, D) -> (num_segments, D) float32 delta.

    E must be a multiple of ``block_e`` and D of ``block_d`` (the ops
    wrapper pads); num_segments should respect the f32 sublane tile
    (multiple of 8) for compiled TPU runs.
    """
    E, D = xs.shape
    be = min(block_e, E)
    bd = min(block_d, D)
    assert E % be == 0 and D % bd == 0, (E, be, D, bd)
    kernel = functools.partial(_kernel, num_segments=num_segments)
    return pl.pallas_call(
        kernel,
        grid=(D // bd, E // be),
        in_specs=[
            pl.BlockSpec((1, be), lambda d, e: (0, e)),
            pl.BlockSpec((1, be), lambda d, e: (0, e)),
            pl.BlockSpec((be, bd), lambda d, e: (e, d)),
            pl.BlockSpec((be, bd), lambda d, e: (e, d)),
        ],
        out_specs=pl.BlockSpec((num_segments, bd), lambda d, e: (0, d)),
        out_shape=jax.ShapeDtypeStruct((num_segments, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(seg.reshape(1, E), w.reshape(1, E), xs, xd)
