"""Plain reference for the LFM2-MoE decoder layer as LiquidAI's
LFM2-8B-A1B publishes it (`config.json`, `model_type: lfm2_moe`, and
the family's modelling code): pre-norm RMSNorm around an OPERATOR and a
feed-forward half, `h = x + op(rms(x; operator_norm))`, `y = h +
ffn(rms(h; ffn_norm))`.

- a `conv` layer's operator: `(B, C, u) = split3(x' W_in)`; `z_t =
  sum_{k=0..2} w[:, k] (B * u)_{t-2+k}`, depthwise and causal, zeros
  before the first token, no bias — here as THREE SHIFTED PRODUCTS;
  `(C * z) W_out`.  No state: the whole sequence every time;
- a `full_attention` layer's: 32 / 8 / 8 heads of 64 without bias, an
  RMS norm over the 64 values of every q and every k head, rotary in
  the half-rotation form on all 64, causal softmax at 1/8, query head
  `h` through KV head `h // 4`, the output projection.  No cache;
- the first `num_dense_layers` layers' ffn a SwiGLU of width 7168; the
  others' `s = sigmoid(x W_g)`, the top 4 of `s + expert_bias` chosen,
  weighted by `s` alone over `sum + 1e-6`, times the scaling factor, a
  SwiGLU of width 1792 an expert, no shared expert;
- `rms(.; embedding_norm)` and the head, which is the embedding
  transposed.

`jax.numpy`, float32, matmul precision `highest`; no cache, no kernels,
no packing, no sorting; nothing from `ray_tpu`.

Departures from the published code, each without effect on a result:
- the experts are walked one at a time over ALL tokens with a per-token
  coefficient (0 where the token did not choose the expert), where the
  published code gathers each expert's tokens: the same sum;
- the convolution is three shifted products where the published code
  calls a grouped `conv1d` with left padding: the same three terms.

One layer at a time on one sequence, as `reference/mistral.py`: the
caller hands it each layer's weights (made from the seed) and carries
the hidden states.  `quant` is the control's hook: every matmul operand
goes through it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROUTE_EPS = 1e-6  # the published router's `sum + 1e-6`


def _identity(x):
    return x


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision="highest")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope_halves(x, theta):
    """x [T, heads, d]: (x1, x2) = halves; rotate by pos * theta^(-2i/d)."""
    T, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(T, dtype=F32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def short_conv(h, w, quant):
    """h [T, D] (already normalised) -> [T, D]."""
    T = h.shape[0]
    b, c, u = jnp.split(_mm(h, w["w_in"], quant), 3, axis=-1)
    bu = b * u
    taps = w["conv_w"]                          # [D, L]
    L = taps.shape[1]
    z = jnp.zeros_like(bu)
    for k in range(L):                          # tap k: L - 1 - k back
        j = L - 1 - k
        z = z + taps[:, k] * jnp.pad(bu, ((j, 0), (0, 0)))[:T]
    return _mm(c * z, w["w_out"], quant)


def attention(h, w, *, n_heads, n_kv, head_dim, theta, eps, quant):
    """h [T, D] (already normalised) -> [T, D]."""
    T = h.shape[0]
    q = _mm(h, w["wq"], quant).reshape(T, n_heads, head_dim)
    k = _mm(h, w["wk"], quant).reshape(T, n_kv, head_dim)
    v = _mm(h, w["wv"], quant).reshape(T, n_kv, head_dim)
    q = rope_halves(rms_norm(q, w["q_norm"], eps), theta)
    k = rope_halves(rms_norm(k, w["k_norm"], eps), theta)
    group = n_heads // n_kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest")
    s = s / jnp.sqrt(jnp.asarray(head_dim, F32))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision="highest")
    return _mm(o.reshape(T, n_heads * head_dim), w["wo"], quant)


def route(h, router, bias, top_k, scale, quant=_identity):
    """(weights [T, k], experts [T, k]): sigmoid scores, the top k of
    `scores + bias`, weights from the scores alone."""
    scores = jax.nn.sigmoid(_mm(h, router, quant))
    _, idx = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS) * scale, idx


def experts(h, w, *, top_k, scale, quant):
    """Routed experts, one at a time over all tokens.  The expert
    stacks may be of any dtype: each expert is cast to float32 when its
    turn comes."""
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
    return y


def layer(x, w, *, n_heads, n_kv, head_dim, theta, eps, top_k, scale,
          quant=_identity):
    """x [T, D] float32 -> [T, D]; `w` one layer's weights, any dtype:
    a convolution's (`w_in`, `conv_w`, `w_out`) or an attention's
    (`wq`, ...) operator, and a dense (`w1`, `w3`, `w2`) or an expert
    (`router`, `router_bias`, `e_gate`, ...) second half."""
    small = {k: v.astype(F32) for k, v in w.items()
             if not k.startswith("e_")}
    h = rms_norm(x, small["op_norm"], eps)
    if "w_in" in w:
        x = x + short_conv(h, small, quant)
    else:
        x = x + attention(h, small, n_heads=n_heads, n_kv=n_kv,
                          head_dim=head_dim, theta=theta, eps=eps,
                          quant=quant)
    h = rms_norm(x, small["ffn_norm"], eps)
    if "router" not in w:
        return x + swiglu(h, small["w1"], small["w3"], small["w2"], quant)
    return x + experts(h, {**w, **small}, top_k=top_k, scale=scale,
                       quant=quant)


def layer_kwargs(model: dict, assumed: dict) -> dict:
    """`layer`'s keywords from the published config's keys (and the
    head width the configuration lists under `assumed`)."""
    return dict(n_heads=model["num_attention_heads"],
                n_kv=model["num_key_value_heads"],
                head_dim=assumed["head_dim"],
                theta=float(model["rope_theta"]), eps=model["norm_eps"],
                top_k=model["num_experts_per_tok"],
                scale=float(model["routed_scaling_factor"]))


def embed(tokens, tok_emb):
    return tok_emb.astype(F32)[tokens]


def head(x, embedding_norm, tok_emb, eps, quant=_identity):
    """x [T, D] -> logits [T, V] float32: the TIED head, the embedding
    transposed."""
    return _mm(rms_norm(x, embedding_norm.astype(F32), eps),
               tok_emb.astype(F32).T, quant)


def forward(tokens, layers, ends, **kw):
    """The whole forward pass of one sequence: tokens [T] -> logits
    [T, V]; `layers` a list of per-layer weights."""
    x = embed(tokens, ends["tok_emb"])
    for w in layers:
        x = layer(x, w, **kw)
    return head(x, ends["embedding_norm"], ends["tok_emb"], kw["eps"])


def margins(logits, served):
    """How far each served token's logit sits below that position's
    largest (0 = it is the reference's own choice)."""
    picked = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return logits.max(axis=-1) - picked
