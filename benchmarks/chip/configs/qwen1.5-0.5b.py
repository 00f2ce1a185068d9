"""Plain float32 reference of qwen1.5-0.5b's training loss: a decoder-only
transformer with RMSNorm, rotary positions (rotate-half), multi-head
attention with q/k/v biases, a SwiGLU MLP and tied embeddings, in
straightforward ``jax.numpy``. It reads sizes from its configuration file
and weights in the program's parameter layout, and imports nothing of the
program."""

import jax
import jax.numpy as jnp


def sizes(c):
    """The file's size keys as a program config ``c`` holds them."""
    return {"hidden_size": c.d_model, "intermediate_size": c.d_ff,
            "num_attention_heads": c.num_heads,
            "num_key_value_heads": c.num_kv_heads,
            "num_hidden_layers": c.num_layers, "vocab_size": c.vocab_size}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, hd): rotate the two halves of each head by position."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, cfg):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a, m = p["attn"], p["mlp"]
    h = _rms(x, p["ln1"]["scale"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, a["wq"]) + a["bq"]
    k = jnp.einsum("bsd,dhk->bshk", h, a["wk"]) + a["bk"]
    v = jnp.einsum("bsd,dhk->bshk", h, a["wv"]) + a["bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    S, hd = x.shape[1], q.shape[-1]
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
    x = x + jnp.einsum("bshk,hkd->bsd", o, a["wo"])
    h = _rms(x, p["ln2"]["scale"], eps)
    up = jnp.einsum("bsd,df->bsf", h, m["wi"])
    gate = jnp.einsum("bsd,df->bsf", h, m["wg"])
    return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, m["wo"])


def loss(params, batch, cfg):
    """Mean next-token cross-entropy of ``batch["tokens"]`` (B, S)."""
    tokens = batch["tokens"]
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    layers = params["units"]["0_attn"]
    x, _ = jax.lax.scan(lambda c, p: (_layer(c, p, cfg), None), x, layers)
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    logits = jnp.einsum("bsd,vd->bsv", x[:, :-1], emb)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(nll)
