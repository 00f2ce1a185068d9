"""Multi-consensus gossip mixing Pallas TPU kernel.

Computes  X <- W^{(R-1)} ... W^{(1)} W^{(0)} X  for a stack of R gossip
matrices (Algorithm 2's hot loop applied to flattened parameters).  The
matrices are tiny (n <= 64) and live in VMEM for the whole grid step; X
streams through in D-tiles so HBM traffic is exactly 2*n*D elements
regardless of R — this is the fusion the multi-consensus structure buys on
TPU (R separate matmuls would read/write X R times).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret


def _kernel(w_ref, x_ref, o_ref, *, rounds):
    x = x_ref[...].astype(jnp.float32)  # (n, bd)

    def body(r, acc):
        # index the (R, n, n) ref, not a loaded value: Mosaic lowers a
        # dynamic ref load but not a dynamic_slice of a VMEM value.
        # HIGHEST: the MXU's default f32 matmul rounds through bf16, which
        # would cost the mixed parameters ~8 mantissa bits per round; the
        # n x n matmul is tiny next to streaming x, so full precision is
        # nearly free
        return jax.lax.dot_general(
            w_ref[r].astype(jnp.float32), acc, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    out = jax.lax.fori_loop(0, rounds, body, x)
    o_ref[...] = out.astype(o_ref.dtype)


def gossip_mix(ws, x, *, block_d=1024, interpret="auto"):
    """ws: (R, n, n); x: (n, D) -> (n, D) after R chained mixings."""
    R, n, _ = ws.shape
    N, D = x.shape
    assert N == n
    bd = min(block_d, D)
    assert D % bd == 0, (D, bd)
    kernel = functools.partial(_kernel, rounds=R)
    return pl.pallas_call(
        kernel,
        grid=(D // bd,),
        in_specs=[
            pl.BlockSpec((R, n, n), lambda d: (0, 0, 0)),
            pl.BlockSpec((n, bd), lambda d: (0, d)),
        ],
        out_specs=pl.BlockSpec((n, bd), lambda d: (0, d)),
        out_shape=jax.ShapeDtypeStruct((n, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
    )(ws, x)
