"""Plain float32 reference of granite-moe-3b-a800m's training loss as the
program defines the model: a decoder-only transformer with RMSNorm, rotary
positions (rotate-half), grouped-query attention without biases, a
mixture-of-experts SwiGLU MLP on every layer and tied embeddings, in
straightforward ``jax.numpy``. Each token goes to the top
``num_experts_per_tok`` experts of a softmax router, their gates
renormalized to sum to one, with no capacity limit; every expert is
computed for every token and weighted by its gate (0 where not chosen).
The loss adds ``aux_loss_weight`` times the Switch load-balance term of
every layer. It reads sizes from its configuration and weights in the
program's parameter layout, and imports nothing of the program.

Besides the reference, the module states what the harness needs to know
of the model: how the leaves the built-in rule does not place are drawn
(``WEIGHTS``), its FLOP count (``forward_flops``) and its size keys
(``sizes``)."""

import jax
import jax.numpy as jnp

import counting

# the router (D, E) and the experts' down-projection (E, F, D): fan-in D
# and F; the rest as the built-in rule draws a dense decoder's leaves
WEIGHTS = {"router": (-2,), "moe/wo": (-2,)}


def sizes(c):
    """The file's size keys as a program config ``c`` holds them."""
    return {"hidden_size": c.d_model, "intermediate_size": c.moe_d_ff,
            "num_attention_heads": c.num_heads,
            "num_key_value_heads": c.num_kv_heads,
            "num_hidden_layers": c.num_layers, "vocab_size": c.vocab_size,
            "num_local_experts": c.num_experts,
            "num_experts_per_tok": c.experts_per_token}


def forward_flops(cfg, tokens):
    """Attention, the router and k experts a token on every layer, and the
    tied unembedding (``counting``'s convention for experts)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = (counting.attn_block(tokens, tokens, d, h, kv, d // h)
             + counting.moe_mlp(tokens, d, cfg["intermediate_size"],
                                cfg["num_local_experts"],
                                cfg["num_experts_per_tok"], gated=True))
    return cfg["num_hidden_layers"] * layer + 2 * tokens * d * v


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


def _attention(a, h, theta):
    """Causal attention; query head i reads key/value head i // (H / KV)."""
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, a["wq"]), theta)
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, a["wk"]), theta)
    v = jnp.einsum("bsd,dhk->bshk", h, a["wv"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    S, hd = h.shape[1], q.shape[-1]
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
    return jnp.einsum("bshk,hkd->bsd", o, a["wo"])


def moe(m, h, k):
    """The expert MLP of tokens ``h`` (B, S, D): (output, load-balance
    term)."""
    x = h.reshape(-1, h.shape[-1])                                # (T, D)
    gates = jax.nn.softmax(x @ m["router"], -1)                   # (T, E)
    E = gates.shape[-1]
    top, idx = jax.lax.top_k(gates, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=x.dtype) * top[..., None],
                     1)                                           # (T, E)
    up = jnp.einsum("td,edf->etf", x, m["wi"])
    gate = jnp.einsum("td,edf->etf", x, m["wg"])
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(gate) * up, m["wo"])
    out = jnp.einsum("te,etd->td", weight, y)
    first = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=x.dtype), 0)
    balance = E * jnp.sum(first * jnp.mean(gates, 0))
    return out.reshape(h.shape), balance


def loss(params, batch, cfg):
    """Mean next-token cross-entropy of ``batch["tokens"]`` (B, S), plus
    ``aux_loss_weight`` times the layers' load-balance terms."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    k = cfg["num_experts_per_tok"]

    def layer(x, p):
        x = x + _attention(p["attn"], _rms(x, p["ln1"]["scale"], eps), theta)
        y, balance = moe(p["moe"], _rms(x, p["ln2"]["scale"], eps), k)
        return x + y, balance

    tokens = batch["tokens"]
    emb = params["embed"]["embedding"]
    x, balance = jax.lax.scan(layer, emb[tokens], params["units"]["0_moe"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = jnp.einsum("bsd,vd->bsv", x[:, :-1], emb)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(nll) + cfg["aux_loss_weight"] * jnp.sum(balance)
