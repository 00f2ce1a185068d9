"""The benchmark's counting functions and peak table, on the CPU.

Model FLOPs are checked against XLA's own count of the reduced-preset
training step with every scan unrolled (a rolled scan's body is counted
once), for every configuration the harness can find, at the sizes its
module's ``sizes`` reads off the program's config. XLA also counts the
elementwise work (norms, softmax, activations, the update, the clip),
which the model count leaves out, so the model count must lie a little
under XLA's: between 85% and 100% of it. The step is compiled at expert
capacity factor 1, where each expert's buffer holds its mean share of the
tokens: XLA then counts the routed work without the capacity padding
that the count leaves out (but with the one-hot dispatch and combine
products, which it counts and the model count does not)."""

import dataclasses
import glob
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


# (nodes, R, seq) a configuration is counted at; others at (2, 1, 32)
SHAPES = {"qwen1.5-0.5b": (2, 1, 128), "whisper-tiny": (4, 2, 48)}


def _configurations():
    """Every configuration the harness can find: the benchmark's and the
    test data's, each as (root, name, nodes, R, seq)."""
    out = []
    for root in (HERE, os.path.join(HERE, "testdata")):
        for path in sorted(glob.glob(os.path.join(root, "configs", "*.json"))):
            name = os.path.basename(path)[:-len(".json")]
            shape = SHAPES.get(name, (2, 1, 32))
            out.append(pytest.param(root, name, *shape,
                                    id="-".join(map(str, (name, *shape)))))
    return out


@pytest.mark.parametrize("root,config,nodes,R,seq", _configurations())
def test_model_flops_against_xla_unrolled(root, config, nodes, R, seq):
    from repro import configs
    from repro.dist import steps as dsteps
    from repro.models import build

    file_cfg = harness.load_json(root, "configs", config + ".json")
    module = harness.config_module(root, config)
    assert hasattr(module, "sizes"), f"configs/{config}.py states no sizes()"
    cfg = dataclasses.replace(configs.get(file_cfg["arch"]).reduced(),
                              unroll=True, moe_capacity_factor=1.0)
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
    mine = counting.step_flops({**file_cfg, **module.sizes(cfg)}, {
        "nodes": nodes, "R": R, "batch": 1, "seq": seq}, module)
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


def test_moe_flops_by_hand():
    # 3 tokens, d=4, experts of width 8 (SwiGLU), 3 experts, 2 a token,
    # one shared expert: router 2*3*4*3 = 72; each expert a token
    # 3 * 2*3*4*8 = 576
    assert counting.moe_mlp(3, 4, 8, experts=3, k=2,
                            gated=True) == 72 + 2 * 576
    assert counting.moe_mlp(3, 4, 8, experts=3, k=2, gated=True, shared=1,
                            shared_f=8) == 72 + 3 * 576
    # a configuration's module counts where it states forward_flops
    moe = harness.config_module(os.path.join(HERE, "testdata"),
                                "granite-moe-3b-a800m")
    cfg = {"hidden_size": 4, "intermediate_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 2, "num_hidden_layers": 1,
           "num_local_experts": 3, "num_experts_per_tok": 2, "vocab_size": 10}
    # attention 384 + 144 as in test_forward_flops_by_hand, unembed 240
    assert counting.forward_flops(cfg, 3, moe) == 528 + 72 + 2 * 576 + 240
    assert counting.step_flops(cfg, {"nodes": 2, "R": 1, "batch": 1,
                                     "seq": 3}, moe) == 3 * 2 * 1992


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
