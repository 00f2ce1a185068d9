"""Compile-only checks of the Pallas gossip kernels for a TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that is
described and not attached: these tests lower each gossip kernel at the
width of one qwen1.5-0.5b MLP matrix (D = 1024 * 2816) for one chip of a
described ``v5e:2x2`` topology, and require the Mosaic kernel
(``tpu_custom_call``) in the compiled HLO.  Interpret mode, which every
other kernel test uses, cannot catch what only the chip's compiler refuses
(unaligned slices, dynamic slices of VMEM values, VMEM overuse).

Only one process may load the TPU library, so the topology is described
inside a module fixture (never at import) and the tests stay in this one
file.  The persistent compilation cache is off around them: a compile for
a described chip can be written to it but not read back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D = 1024 * 2816   # one qwen1.5-0.5b MLP matrix, flattened
ROUNDS = 3


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jax install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _case(kernel: str, n: int, chip):
    """(fn, shape args) for one kernel at n nodes; ``interpret=False``
    because the CPU backend would otherwise pick interpret mode."""
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt,
                                                            sharding=chip)
    ws, x = S((ROUNDS, n, n)), S((n, D))
    if kernel == "gossip":
        return (lambda w, v: ops.gossip_mix(w, v, use_pallas=True,
                                            interpret=False)), (ws, x)
    if kernel.startswith("quantized-"):
        scheme = kernel.split("-", 1)[1]
        return (lambda w, v, r: ops.quantized_gossip_mix(
            w, v, r, scheme=scheme, use_pallas=True,
            interpret=False)), (ws, x, S((n, D)))
    assert kernel == "sparse"
    E = n  # a perfect matching, both directions: one edge per receiver
    idx = S((E,), jnp.int32)
    return (lambda v, src, dst, w, seg, slots: ops.sparse_gossip_mix(
        v, src, dst, w, seg, slots, use_pallas=True, interpret=False)), \
        (x, idx, idx, S((E,)), idx, S((n,), jnp.int32))


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("kernel", ["gossip", "quantized-sign",
                                    "quantized-int8", "sparse"])
def test_gossip_kernel_compiles_for_v5e(kernel, n, one_chip):
    fn, args = _case(kernel, n, one_chip)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
