"""The benchmark's counting functions and peak table, on the CPU.

Model FLOPs are checked against XLA's own count of the reduced-preset
training step with every scan unrolled (a rolled scan's body is counted
once). XLA also counts the elementwise work (norms, softmax, activations,
the update, the clip), which the model count leaves out, so the model
count must lie a little under XLA's: between 85% and 100% of it."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))
sys.path.insert(0, HERE)

import counting  # noqa: E402
import harness  # noqa: E402


def _config_json(cfg) -> dict:
    """The reduced program config in the configuration files' keys."""
    if cfg.arch_type == "audio":
        return {"d_model": cfg.d_model, "encoder_layers": cfg.encoder_layers,
                "decoder_layers": cfg.num_layers,
                "encoder_attention_heads": cfg.num_heads,
                "decoder_attention_heads": cfg.num_heads,
                "encoder_ffn_dim": cfg.d_ff, "decoder_ffn_dim": cfg.d_ff,
                "vocab_size": cfg.vocab_size,
                "max_source_positions": cfg.encoder_seq}
    return {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "num_hidden_layers": cfg.num_layers,
            "vocab_size": cfg.vocab_size, "hidden_act": "silu"}


@pytest.mark.parametrize("arch,nodes,R,seq", [
    ("qwen1.5-0.5b", 2, 1, 128),
    ("whisper-tiny", 4, 2, 48),
])
def test_model_flops_against_xla_unrolled(arch, nodes, R, seq):
    from repro import configs
    from repro.dist import steps as dsteps
    from repro.models import build

    cfg = dataclasses.replace(configs.get(arch).reduced(), unroll=True)
    init, _, step = dsteps.make_train_step(build(cfg), cfg, algo="mc_dsgt",
                                           gamma=0.05, R=R, unroll=True)
    state = jax.eval_shape(lambda k: init(k, nodes, jnp.float32),
                           jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((nodes, R, 1, seq), jnp.int32)}
    if cfg.arch_type == "audio":
        batch["frames"] = jax.ShapeDtypeStruct(
            (nodes, R, 1, cfg.encoder_seq, cfg.d_model), jnp.float32)
    weights = jax.ShapeDtypeStruct((2 * R, nodes, nodes), jnp.float32)
    xla = jax.jit(step).lower(state, batch, weights).compile() \
        .cost_analysis()["flops"]
    mine = counting.step_flops(_config_json(cfg), {
        "nodes": nodes, "R": R, "batch": 1, "seq": seq})
    assert 0.85 * xla <= mine <= xla


def test_forward_flops_by_hand():
    # one layer, d=4, 2 heads of 2, ffn 8 (SwiGLU), vocab 10, 3 tokens:
    # q,k,v,o 4 * 2*3*4*4 = 384; scores + values 2 * 2*3*3*4 = 144;
    # mlp 3 * 2*3*4*8 = 576; unembed 2*3*4*10 = 240
    cfg = {"hidden_size": 4, "intermediate_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 2, "num_hidden_layers": 1,
           "vocab_size": 10, "hidden_act": "silu"}
    assert counting.forward_flops(cfg, 3) == 384 + 144 + 576 + 240
    assert counting.step_flops(cfg, {"nodes": 2, "R": 2, "batch": 1,
                                     "seq": 3}) == 3 * 4 * 1344


def test_positions_per_step():
    qwen = harness.load_json(HERE, "configs", "qwen1.5-0.5b.json")
    whisper = harness.load_json(HERE, "configs", "whisper-tiny.json")
    t = {"nodes": 8, "R": 2, "batch": 1, "seq": 448}
    assert counting.positions_per_step(whisper, t) == 16 * (448 + 1500)
    assert counting.positions_per_step(qwen, t) == 16 * 448


def test_gossip_kernel_cost_by_hand():
    # n=8 rows of d=2500 f32 padded to 3 blocks of 1024: read + write
    # 2*8*3072*4 bytes, plus 2 (8, 8) f32 weight matrices; 2 rounds of
    # an (8, 8) x (8, 3072) product
    c = counting.gossip_kernel_cost(8, 2500, 2)
    assert c["bytes"] == 2 * 8 * 3072 * 4 + 2 * 64 * 4
    assert c["flops"] == 2 * 2 * 64 * 3072


def test_full_size_step_flops():
    qwen = harness.load_json(HERE, "configs", "qwen1.5-0.5b.json")
    f = counting.step_flops(qwen, {"nodes": 2, "R": 1, "batch": 1,
                                   "seq": 128})
    assert 7.0e11 < f < 7.4e11


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peaks_for("TPU v9 imaginary")
