"""Fused quantize -> mix -> dequantize -> residual-update Pallas TPU kernel.

The compressed-gossip hot loop (error feedback a la Bagua's low-precision
decentralized algorithm) applied to the flattened (n, D) stacked state:

    for r in range(R):
        buf = x + res                       # error-feedback compensation
        q   = dequant(quant(buf))           # what the wire actually carries
        res = buf - q                       # residual for the next round
        x   = W[r] @ q                      # the gossip mixing itself

An unfused implementation pays one HBM round-trip of the state per stage
per round; here the R-round loop runs entirely in VMEM per D-tile, so HBM
traffic is exactly 2*(x + res) regardless of R — the same fusion the plain
``gossip_matmul`` kernel buys, extended to the quantization stages.  The
quantization math itself is :func:`repro.kernels.ref.quantize_dequantize_ref`
(pure jnp, shared with the oracle and the host path), so the kernel can
never drift from the reference scheme.

Blocking: ``block_d`` must be a multiple of ``group`` so a tile always
holds whole quantization groups — block boundaries then never change the
per-group scales and any legal ``block_d`` is bit-identical to the
reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .interpret import resolve_interpret


def _kernel(w_ref, x_ref, r_ref, o_ref, ro_ref, *, rounds, scheme, group,
            error_feedback):
    x = x_ref[...].astype(jnp.float32)        # (n, bd)
    res = r_ref[...].astype(jnp.float32)      # (n, bd)

    def body(r, carry):
        e, rs = carry
        buf = e + rs
        deq, err = ref.quantize_dequantize_ref(buf, scheme=scheme,
                                               group=group)
        if error_feedback:  # static: selects the traced graph, not a cond
            rs = err
        # w_ref[r] and HIGHEST: see gossip_matmul._kernel
        e = jax.lax.dot_general(
            w_ref[r].astype(jnp.float32), deq, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return e, rs

    out, rs = jax.lax.fori_loop(0, rounds, body, (x, res))
    o_ref[...] = out.astype(o_ref.dtype)
    ro_ref[...] = rs.astype(ro_ref.dtype)


def quantized_gossip_mix(ws, x, res, *, scheme, group=256,
                         error_feedback=True, block_d=1024, interpret="auto"):
    """ws: (R, n, n); x, res: (n, D) -> (mixed x, final residual).

    D must be a multiple of ``group`` (callers pad; zero columns are a
    fixed point of quantize/mix/residual, so padding is exact) and
    ``block_d`` is rounded down to a multiple of ``group``.
    """
    R, n, _ = ws.shape
    N, D = x.shape
    assert N == n and res.shape == (n, D), (x.shape, res.shape, ws.shape)
    assert D % group == 0, (D, group)
    bd = min(block_d, D)
    bd = max(group, (bd // group) * group)
    assert D % bd == 0, (D, bd)
    kernel = functools.partial(_kernel, rounds=R, scheme=scheme, group=group,
                               error_feedback=error_feedback)
    return pl.pallas_call(
        kernel,
        grid=(D // bd,),
        in_specs=[
            pl.BlockSpec((R, n, n), lambda d: (0, 0, 0)),
            pl.BlockSpec((n, bd), lambda d: (0, d)),
            pl.BlockSpec((n, bd), lambda d: (0, d)),
        ],
        out_specs=(
            pl.BlockSpec((n, bd), lambda d: (0, d)),
            pl.BlockSpec((n, bd), lambda d: (0, d)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, D), x.dtype),
            jax.ShapeDtypeStruct((n, D), res.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
    )(ws, x, res)
