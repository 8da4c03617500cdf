"""Plain reference for the Mistral-7B decoder (Jiang et al. 2023,
arXiv:2310.06825; `mistralai/Mistral-7B-v0.3` `config.json`): pre-norm
RMSNorm, rotary embeddings (rotate-half form, as the published
implementation), grouped-query attention with a causal mask, SwiGLU,
untied head.  `jax.numpy`, float32, matmul precision `highest`; no
cache, no kernels, no batching; nothing from `ray_tpu`.

It works one layer at a time on one sequence, because the float32
model does not fit beside a serving engine: the caller hands it each
layer's weights (made from the seed) and carries the hidden states.
`quant` is the control's hook: every matmul operand goes through it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _identity(x):
    return x


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision="highest")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """x [T, H, hd]: rotate pairs (i, i + hd/2) by pos * theta^(-2i/hd)."""
    T, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, w, *, n_heads, n_kv_heads, head_dim, rope_theta, eps,
          quant=_identity):
    """x [T, E] float32 -> [T, E]; `w` one layer's weights, any dtype."""
    w = {k: v.astype(F32) for k, v in w.items()}
    T = x.shape[0]
    h = rms_norm(x, w["attn_norm"], eps)
    q = rope(_mm(h, w["wq"], quant).reshape(T, n_heads, head_dim), rope_theta)
    k = rope(_mm(h, w["wk"], quant).reshape(T, n_kv_heads, head_dim),
             rope_theta)
    v = _mm(h, w["wv"], quant).reshape(T, n_kv_heads, head_dim)
    group = n_heads // n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest")
    s = s / jnp.sqrt(jnp.asarray(head_dim, F32))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision="highest")
    x = x + _mm(o.reshape(T, n_heads * head_dim), w["wo"], quant)
    h = rms_norm(x, w["mlp_norm"], eps)
    gate, up = _mm(h, w["w_gate"], quant), _mm(h, w["w_up"], quant)
    return x + _mm(jax.nn.silu(gate) * up, w["w_down"], quant)


def embed(tokens, tok_emb):
    return tok_emb.astype(F32)[tokens]


def head(x, final_norm, lm_head, eps, quant=_identity):
    """x [T, E] -> logits [T, V] float32."""
    return _mm(rms_norm(x, final_norm.astype(F32), eps),
               lm_head.astype(F32), quant)


def margins(logits, served):
    """How far each served token's logit sits below that position's
    largest (0 = it is the reference's own choice)."""
    picked = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return logits.max(axis=-1) - picked
