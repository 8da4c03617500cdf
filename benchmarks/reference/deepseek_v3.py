"""Plain reference for the DeepSeek-V3 decoder layer as kakaocorp's
kanana-2-30b-a3b publishes it (`config.json`, `model_type: deepseek_v3`,
`q_lora_rank` null; DeepSeek-AI, arXiv:2412.19437 and 2405.04434):
pre-norm RMSNorm, multi-head latent attention in its EXPANDED form only
(the compressed KV goes through `W_kvb` to per-head keys and values;
nothing is absorbed, nothing is cached), one leading dense SwiGLU
layer, then expert layers: sigmoid router with a correction bias, top-k
of `scores + bias`, weights from the scores normalised and scaled, a
SwiGLU per expert, plus one always-on shared SwiGLU.  `jax.numpy`,
float32, matmul precision `highest`; no cache, no kernels, no sorting,
no batching; nothing from `ray_tpu`.

Departures from the published code, each without effect on a result:
- rotary embedding rotates the pairs (2i, 2i + 1) IN PLACE, where the
  published code (`rope_interleave`) first permutes each vector to
  [evens | odds] and rotates halves; queries and keys take the same
  permutation there, so every score is the same;
- the experts are walked one at a time over ALL tokens with a
  per-token coefficient (0 where the token did not choose the expert),
  where the published code gathers each expert's tokens: the same sum,
  and no token can be dropped by construction;
- `n_group` = `topk_group` = 1, so the group-limited step of
  `noaux_tc` is the identity and is left out.

One layer at a time on one sequence, as `reference/mistral.py`: the
caller hands it each layer's weights (made from the seed) and carries
the hidden states.  `quant` is the control's hook: every matmul
operand goes through it (the router's too).
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


def rope_pairs(x, theta):
    """x [T, ..., d]: rotate the pair (2i, 2i + 1) by pos * theta^(-2i/d)."""
    T, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def attention(h, w, *, n_heads, nope, rope, v_dim, rank, theta, eps, quant):
    """h [T, D] (already normalised) -> [T, n_heads * v_dim]."""
    T = h.shape[0]
    q = _mm(h, w["wq"], quant).reshape(T, n_heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], theta)], -1)
    kv_a = _mm(h, w["wkv_a"], quant)
    c = rms_norm(kv_a[:, :rank], w["kv_norm"], eps)
    k_rope = rope_pairs(kv_a[:, rank:], theta)          # one a token
    kv = _mm(c, w["wkv_b"], quant).reshape(T, n_heads, nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, None, :], (T, n_heads, rope))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest")
    s = s / jnp.sqrt(jnp.asarray(nope + rope, F32))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, kv[..., nope:], precision="highest")
    return o.reshape(T, n_heads * v_dim)


def route(h, router, bias, top_k, scale, quant=_identity):
    """(weights [T, k], experts [T, k]): sigmoid scores, the top k of
    `scores + bias`, weights from the scores alone."""
    scores = jax.nn.sigmoid(_mm(h, router, quant))
    _, idx = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale, idx


def experts(h, w, *, top_k, scale, quant):
    """Routed experts, one at a time over all tokens, plus the shared
    expert.  The expert stacks may be of any dtype: each expert is cast
    to float32 when its turn comes."""
    weights, idx = route(h, w["router"].astype(F32),
                         w["router_bias"].astype(F32), top_k, scale, quant)

    def one(y, inputs):
        e, gate, up, down = inputs
        coef = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)  # [T]
        out = swiglu(h, gate.astype(F32), up.astype(F32), down.astype(F32),
                     quant)
        return y + coef[:, None] * out, None

    E = w["e_gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(E), w["e_gate"], w["e_up"], w["e_down"]))
    return y + swiglu(h, w["s_gate"].astype(F32), w["s_up"].astype(F32),
                      w["s_down"].astype(F32), quant)


def layer(x, w, *, n_heads, nope, rope, v_dim, rank, theta, eps, top_k,
          scale, quant=_identity):
    """x [T, D] float32 -> [T, D]; `w` one layer's weights, any dtype:
    a dense layer's (`w_gate`, ...) or an expert layer's (`router`,
    `e_gate`, ..., `s_gate`, ...)."""
    small = {k: v.astype(F32) for k, v in w.items()
             if not k.startswith("e_")}
    h = rms_norm(x, small["attn_norm"], eps)
    o = attention(h, small, n_heads=n_heads, nope=nope, rope=rope,
                  v_dim=v_dim, rank=rank, theta=theta, eps=eps, quant=quant)
    x = x + _mm(o, small["wo"], quant)
    h = rms_norm(x, small["mlp_norm"], eps)
    if "router" not in w:
        return x + swiglu(h, small["w_gate"], small["w_up"], small["w_down"],
                          quant)
    return x + experts(h, {**w, **small}, top_k=top_k, scale=scale,
                       quant=quant)


def layer_kwargs(model: dict) -> dict:
    """`layer`'s keywords from the published config's keys."""
    return dict(n_heads=model["num_attention_heads"],
                nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"],
                v_dim=model["v_head_dim"], rank=model["kv_lora_rank"],
                theta=float(model["rope_theta"]), eps=model["rms_norm_eps"],
                top_k=model["num_experts_per_tok"],
                scale=model["routed_scaling_factor"])


def embed(tokens, tok_emb):
    return tok_emb.astype(F32)[tokens]


def head(x, final_norm, lm_head, eps, quant=_identity):
    """x [T, D] -> logits [T, V] float32."""
    return _mm(rms_norm(x, final_norm.astype(F32), eps),
               lm_head.astype(F32), quant)


def margins(logits, served):
    """How far each served token's logit sits below that position's
    largest (0 = it is the reference's own choice)."""
    picked = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return logits.max(axis=-1) - picked
