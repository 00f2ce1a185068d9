"""Operations and bytes, counted from a configuration file and a cell's
shapes. Kept with the benchmark so that no change to the program can move
the yardstick.

Model FLOPs count the matrix multiplications of one forward and one
backward pass (backward = 2x forward), two FLOPs per multiply-add, with no
recomputation. Attention scores and values count the full S x S product,
causal or not (the PaLM convention).
"""

from __future__ import annotations


def _attn_block(sq: int, sk: int, d: int, heads: int, kv_heads: int,
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


def _mlp(s: int, d: int, f: int, gated: bool) -> int:
    return (3 if gated else 2) * 2 * s * d * f


def forward_flops(cfg: dict, tokens: int) -> int:
    """Forward matmul FLOPs of one sequence of ``tokens`` decoder tokens
    (plus the configuration's encoder frames, for an encoder-decoder)."""
    if "encoder_layers" in cfg:
        d, v = cfg["d_model"], cfg["vocab_size"]
        se, hd = cfg["max_source_positions"], d // cfg["encoder_attention_heads"]
        eh, dh = cfg["encoder_attention_heads"], cfg["decoder_attention_heads"]
        enc = cfg["encoder_layers"] * (
            _attn_block(se, se, d, eh, eh, hd)
            + _mlp(se, d, cfg["encoder_ffn_dim"], gated=False))
        dec = cfg["decoder_layers"] * (
            _attn_block(tokens, tokens, d, dh, dh, hd)
            + _attn_block(tokens, se, d, dh, dh, hd, kv_from=se)
            + _mlp(tokens, d, cfg["decoder_ffn_dim"], gated=False))
        return enc + dec + 2 * tokens * d * v
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = (_attn_block(tokens, tokens, d, h, kv, d // h)
             + _mlp(tokens, d, cfg["intermediate_size"],
                    gated=cfg["hidden_act"] == "silu"))
    return cfg["num_hidden_layers"] * layer + 2 * tokens * d * v


def step_flops(cfg: dict, traffic: dict) -> int:
    """Model FLOPs of one training step: every node's R microbatches of
    ``batch`` sequences, forward and backward."""
    seqs = traffic["nodes"] * traffic["R"] * traffic["batch"]
    return 3 * seqs * forward_flops(cfg, traffic["seq"])


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
