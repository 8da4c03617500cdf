"""Plain reference for the Brumby-14B-Base decoder layer: the Qwen3
block (pre-norm RMSNorm, grouped query heads, per-head `q_norm` and
`k_norm`, rotary in halves over the whole head, SwiGLU, untied head)
with every attention replaced by POWER RETENTION of degree 2 (Manifest
AI, "Scaling Context Requires Rethinking Attention", arXiv:2507.04239;
`manifestai/Brumby-14B-Base` and its `retention` package).

Retention is written here in its QUADRATIC form only: for query head
`i` reading KV head `i // group`, with the log-gate `g_t = log
sigmoid(h_t W_g + b_g)` (one a KV head) and `G_t` its running sum,

    w_ts = exp(G_t - G_s) (q_t . k_s)^2   for s <= t
    o_t  = sum_s w_ts v_s / (sum_s w_ts + eps)

No state, no monomials, no chunks, no cache, no kernels; `jax.numpy`,
float32, matmul precision `highest`; nothing from `ray_tpu`.  No
softmax and no 1/sqrt(d): a scale of `q . k` cancels between numerator
and denominator.

ASSUMED (the published `config.json` is the Qwen3-14B shape key for key
and carries no key of the retention; from the paper and the package):
degree 2; the gate one scalar a KV head from a `[hidden, kv_heads]`
projection with a bias; the normaliser the gated sum of the weights
(the package's `sum_of_keys`); `eps` 1e-6; `q_norm`, `k_norm` and
rotary kept from the Qwen3 block.

It works one layer at a time on one sequence, as `reference/
mistral.py`, and in BLOCKS so that it fits beside the resident model:
the scores one KV head at a time, the MLP `rows` tokens at a time, the
head `vocab_block` columns at a time (`head_margins`).  `quant` is the
control's hook: every matmul operand of the layer goes through it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _identity(x):
    return x


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b.astype(F32)), precision="highest")


def rms_norm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * g.astype(F32))


def rope(x, theta):
    """x [T, H, hd]: rotate pairs (i, i + hd/2) by pos * theta^(-2i/hd)."""
    T, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def retention(q, k, v, g, eps):
    """q [T, H, d], k, v [T, KV, d], g [T, KV] -> [T, H, d]; one KV
    head (and its query heads) at a time."""
    T, H, d = q.shape
    KV = k.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    G = jnp.cumsum(g, axis=0)                                  # [T, KV]

    def one(args):
        qh, kh, vh, Gh = args           # [T, group, d], [T, d], [T, d], [T]
        s = jnp.einsum("tgd,sd->gts", qh, kh, precision="highest")
        decay = jnp.where(causal, jnp.exp(jnp.where(
            causal, Gh[:, None] - Gh[None, :], 0.0)), 0.0)
        w = decay[None] * s * s
        num = jnp.einsum("gts,sd->tgd", w, vh, precision="highest")
        return num / (jnp.sum(w, axis=-1).T[:, :, None] + eps)

    o = jax.lax.map(one, (
        q.reshape(T, KV, H // KV, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2), G.T))
    return o.transpose(1, 0, 2, 3).reshape(T, H, d)


def layer(x, w, *, n_heads, n_kv_heads, head_dim, rope_theta, eps,
          retention_eps, rows=512, quant=_identity):
    """x [T, E] float32 -> [T, E]; `w` one layer's weights, any dtype.
    `rows`: tokens the MLP takes at a time (T a multiple of it, or
    smaller)."""
    T = x.shape[0]
    h = rms_norm(x, w["attn_norm"], eps)
    q = _mm(h, w["wq"], quant).reshape(T, n_heads, head_dim)
    k = _mm(h, w["wk"], quant).reshape(T, n_kv_heads, head_dim)
    v = _mm(h, w["wv"], quant).reshape(T, n_kv_heads, head_dim)
    q = rope(rms_norm(q, w["q_norm"], eps), rope_theta)
    k = rope(rms_norm(k, w["k_norm"], eps), rope_theta)
    g = jax.nn.log_sigmoid(_mm(h, w["wg"], _identity) + w["bg"].astype(F32))
    o = retention(q, k, v, g, retention_eps)
    x = x + _mm(o.reshape(T, n_heads * head_dim), w["wo"], quant)

    def mlp(xb):
        hb = rms_norm(xb, w["mlp_norm"], eps)
        return xb + _mm(jax.nn.silu(_mm(hb, w["w_gate"], quant))
                        * _mm(hb, w["w_up"], quant), w["w_down"], quant)

    if T <= rows or T % rows:
        return mlp(x)
    return jax.lax.map(mlp, x.reshape(T // rows, rows, -1)).reshape(T, -1)


def layer_kwargs(model: dict, assumed: dict) -> dict:
    """`layer`'s keywords from the published config's keys and the
    configuration's `assumed`."""
    return dict(n_heads=model["num_attention_heads"],
                n_kv_heads=model["num_key_value_heads"],
                head_dim=model["head_dim"],
                rope_theta=float(model["rope_theta"]),
                eps=model["rms_norm_eps"],
                retention_eps=float(assumed["retention_eps"]))


def embed(tokens, tok_emb):
    return tok_emb[tokens].astype(F32)


def head(x, final_norm, lm_head, eps):
    """x [T, E] -> logits [T, V] float32 (a small vocabulary's)."""
    return _mm(rms_norm(x, final_norm, eps), lm_head, _identity)


def forward(tokens, layers, ends, **kw):
    """The whole forward pass of one sequence: tokens [T] -> logits
    [T, V]; `layers` a list of per-layer weights."""
    x = embed(tokens, ends["tok_emb"])
    for w in layers:
        x = layer(x, w, **kw)
    return head(x, ends["final_norm"], ends["lm_head"], kw["eps"])


def head_margins(x, final_norm, lm_head, eps, served, vocab_block):
    """How far each served token's logit sits below that position's
    largest (0 = it is the reference's own choice), and the logits'
    standard deviation: x [T, E], served [T] -> ([T], scalar).  The
    head is taken `vocab_block` columns at a time (V a multiple of it):
    the float32 head of a 152k vocabulary does not fit beside the
    model."""
    h = rms_norm(x, final_norm, eps)
    E, V = lm_head.shape
    blocks = lm_head.reshape(E, V // vocab_block, vocab_block)

    def one(carry, args):
        top, picked, s1, s2 = carry
        i, wb = args
        lg = _mm(h, wb, _identity)                              # [T, Vb]
        at = served - i * vocab_block
        mine = (at >= 0) & (at < vocab_block)
        got = jnp.take_along_axis(
            lg, jnp.clip(at, 0, vocab_block - 1)[:, None], axis=-1)[:, 0]
        return (jnp.maximum(top, lg.max(axis=-1)),
                jnp.where(mine, got, picked),
                s1 + lg.sum(), s2 + (lg * lg).sum()), None

    T = x.shape[0]
    init = (jnp.full((T,), -jnp.inf, F32), jnp.zeros((T,), F32),
            jnp.zeros((), F32), jnp.zeros((), F32))
    (top, picked, s1, s2), _ = jax.lax.scan(
        one, init, (jnp.arange(V // vocab_block),
                    blocks.transpose(1, 0, 2)))
    n = T * V
    return top - picked, jnp.sqrt(jnp.maximum(s2 / n - (s1 / n) ** 2, 0.0))
