"""Plain float32 reference of whisper-tiny's training loss as the program
defines the model: a bidirectional encoder over frame embeddings and a
causal decoder with cross-attention, pre-LayerNorm blocks with no linear
biases, tanh-GELU MLPs, sinusoidal positions in both stacks and tied
embeddings, in straightforward ``jax.numpy``. It reads sizes from its
configuration file and weights in the program's parameter layout, and
imports nothing of the program."""

import math

import jax
import jax.numpy as jnp

EPS = 1e-6


def sizes(c):
    """The file's size keys as a program config ``c`` holds them."""
    return {"d_model": c.d_model, "encoder_layers": c.encoder_layers,
            "decoder_layers": c.num_layers,
            "encoder_attention_heads": c.num_heads,
            "decoder_attention_heads": c.num_heads,
            "encoder_ffn_dim": c.d_ff, "decoder_ffn_dim": c.d_ff,
            "vocab_size": c.vocab_size, "max_source_positions": c.encoder_seq}


def _ln(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _sinusoids(length, width):
    half = width // 2
    inc = math.log(10_000.0) / (half - 1)
    freq = jnp.exp(-inc * jnp.arange(half, dtype=jnp.float32))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freq[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _attend(p, xq, xkv, causal):
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"])
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) * q.shape[-1] ** -0.5
    if causal:
        n = xq.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _mlp(p, x):
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wi"]), approximate=True)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


def _enc_layer(x, p):
    h = _ln(x, p["ln1"])
    x = x + _attend(p["attn"], h, h, causal=False)
    return x + _mlp(p["mlp"], _ln(x, p["ln2"])), None


def loss(params, batch, cfg):
    """Mean next-token cross-entropy of ``batch["tokens"]`` (B, S) given
    ``batch["frames"]`` (B, Se, D)."""
    tokens, frames = batch["tokens"], batch["frames"]
    d = frames.shape[-1]
    x = frames + _sinusoids(frames.shape[1], d).astype(frames.dtype)[None]
    x, _ = jax.lax.scan(_enc_layer, x, params["enc"])
    enc = _ln(x, params["enc_norm"])

    def dec_layer(y, p):
        h = _ln(y, p["ln1"])
        y = y + _attend(p["self"], h, h, causal=True)
        y = y + _attend(p["cross"], _ln(y, p["ln_x"]), enc, causal=False)
        return y + _mlp(p["mlp"], _ln(y, p["ln2"])), None

    emb = params["embed"]["embedding"]
    y = emb[tokens] + _sinusoids(tokens.shape[1], d).astype(emb.dtype)[None]
    y, _ = jax.lax.scan(dec_layer, y, params["dec"])
    y = _ln(y, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", y[:, :-1], emb)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(nll)
