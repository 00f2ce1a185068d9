"""Operations and bytes, counted from a configuration file and a cell's
shapes. Kept with the benchmark so that no change to the program can move
the yardstick.

Model FLOPs count the matrix multiplications of one forward and one
backward pass (backward = 2x forward), two FLOPs per multiply-add, with no
recomputation. Attention scores and values count the full S x S product,
causal or not (the PaLM convention). A mixture of experts counts, for
every token, its k routed experts, the shared experts and the router's
product; the padding of the experts' capacity buffers, and the one-hot
dispatch and combine products that fill and empty them, are not counted.

A configuration whose module ``configs/<config>.py`` states
``forward_flops(cfg, tokens)`` is counted by it; the others by the built-in
count of a dense decoder or of whisper's encoder-decoder. The helpers
below are for such modules to build on.
"""

from __future__ import annotations


def attn_block(sq: int, sk: int, d: int, heads: int, kv_heads: int,
               head_dim: int, kv_from: int | None = None) -> int:
    """Forward FLOPs of one attention block: q/k/v/o projections, scores
    and the weighted sum. ``kv_from`` is the length of the k/v source when
    it differs from the queries' (cross-attention)."""
    kv_len = sk if kv_from is None else kv_from
    q = 2 * sq * d * heads * head_dim
    kv = 2 * 2 * kv_len * d * kv_heads * head_dim
    o = 2 * sq * heads * head_dim * d
    scores = 2 * sq * sk * heads * head_dim
    pv = 2 * sq * sk * heads * head_dim
    return q + kv + o + scores + pv


def mlp(s: int, d: int, f: int, gated: bool) -> int:
    return (3 if gated else 2) * 2 * s * d * f


def moe_mlp(s: int, d: int, f: int, experts: int, k: int, gated: bool,
            shared: int = 0, shared_f: int = 0) -> int:
    """Forward FLOPs of a mixture-of-experts MLP over ``s`` tokens: the
    router's (d, experts) product, k experts of width ``f`` a token and
    ``shared`` always-on experts of width ``shared_f``."""
    return (2 * s * d * experts + k * mlp(s, d, f, gated)
            + shared * mlp(s, d, shared_f or f, gated))


def whisper_flops(cfg: dict, tokens: int) -> int:
    """An encoder-decoder over the configuration's encoder frames and
    ``tokens`` decoder tokens, with tied unembedding."""
    d, v = cfg["d_model"], cfg["vocab_size"]
    se, hd = cfg["max_source_positions"], d // cfg["encoder_attention_heads"]
    eh, dh = cfg["encoder_attention_heads"], cfg["decoder_attention_heads"]
    enc = cfg["encoder_layers"] * (
        attn_block(se, se, d, eh, eh, hd)
        + mlp(se, d, cfg["encoder_ffn_dim"], gated=False))
    dec = cfg["decoder_layers"] * (
        attn_block(tokens, tokens, d, dh, dh, hd)
        + attn_block(tokens, se, d, dh, dh, hd, kv_from=se)
        + mlp(tokens, d, cfg["decoder_ffn_dim"], gated=False))
    return enc + dec + 2 * tokens * d * v


def dense_decoder_flops(cfg: dict, tokens: int) -> int:
    """A decoder with an MLP of ``intermediate_size`` on every layer."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = (attn_block(tokens, tokens, d, h, kv, d // h)
             + mlp(tokens, d, cfg["intermediate_size"],
                   gated=cfg["hidden_act"] == "silu"))
    return cfg["num_hidden_layers"] * layer + 2 * tokens * d * v


def forward_flops(cfg: dict, tokens: int, model=None) -> int:
    """Forward matmul FLOPs of one sequence of ``tokens`` decoder tokens
    (plus the configuration's encoder frames, for an encoder-decoder), by
    the configuration's module ``model`` where it states a count."""
    if model is not None and hasattr(model, "forward_flops"):
        return model.forward_flops(cfg, tokens)
    if "encoder_layers" in cfg:
        return whisper_flops(cfg, tokens)
    return dense_decoder_flops(cfg, tokens)


def step_flops(cfg: dict, traffic: dict, model=None) -> int:
    """Model FLOPs of one training step: every node's R microbatches of
    ``batch`` sequences, forward and backward. ``model`` is the
    configuration's module, whose ``forward_flops`` counts where it has
    one."""
    seqs = traffic["nodes"] * traffic["R"] * traffic["batch"]
    return 3 * seqs * forward_flops(cfg, traffic["seq"], model)


def positions_per_step(cfg: dict, traffic: dict) -> int:
    """Input positions the gradient oracles consume in one step: decoder
    tokens, plus encoder frames for an encoder-decoder."""
    per_seq = traffic["seq"] + cfg.get("max_source_positions", 0) \
        * ("encoder_layers" in cfg)
    return traffic["nodes"] * traffic["R"] * traffic["batch"] * per_seq


def gossip_kernel_cost(n: int, d: int, rounds: int, itemsize: int = 4,
                       block_d: int = 1024) -> dict:
    """One call of the fused gossip kernel on an ``(n, d)`` state padded to
    a multiple of ``block_d``: it reads and writes the state once (the
    (R, n, n) weights stay in VMEM) and runs R chained (n, n) x (n, bd)
    products per block."""
    dp = -(-d // block_d) * block_d
    return {"bytes": 2 * n * dp * itemsize + rounds * n * n * 4,
            "flops": rounds * 2 * n * n * dp}
