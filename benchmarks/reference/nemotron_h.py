"""Plain reference for the decoder layer of
`nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16` (`config.json`,
`model_type: nemotron_h`): every layer ONE mixer behind a pre-norm, `x =
x + mixer(RMSNorm(x))`, of the kind `hybrid_override_pattern` names.

- `M`, Mamba-2 (Dao and Gu 2024): `in_proj` to a gate `z`, `xBC` and
  `dt`; a causal depthwise convolution over `xBC` written as `conv_kernel`
  SHIFTED PRODUCTS, plus bias, then SiLU; `x` `[heads, head_dim]`, `B`,
  `C` `[n_groups, ssm_state_size]` (a group's heads share them); `dt =
  softplus(dt + dt_bias)`, `A = -exp(A_log)`; the recurrence ONE TOKEN
  AT A TIME, `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t = S_t C_t
  + D x_t` (a `lax.scan` over the tokens: no chunks, no cumulative sums,
  no masked products); `RMSNorm(y * silu(z))` in `n_groups` groups with a
  gain; `out_proj`;
- `*`, attention: grouped queries, causal softmax at `1 / sqrt(head_dim)`
  over the whole sequence, NO rotation, `o_proj`; no cache;
- `E`, experts in a latent width: sigmoid router with a correction bias
  over ALL the router's experts, the top-k of `scores + bias`, weights
  from the scores normalised (`+ 1e-20`) and scaled; `u = h W_in`; the
  experts one at a time, `W2_e relu(W1_e u)^2`, of which only those this
  chip HOLDS exist (`offset`: the first held expert; a pair routed
  elsewhere adds nothing, as in the deployment, whose other chips add
  their parts); the weighted sum through `W_out`; one shared expert
  `W2_s relu(W1_s h)^2` on the `hidden_size`-wide input beside it.

`jax.numpy`, float32, matmul precision `highest`; no state carried from
anywhere, no cache, no kernels, no sorting; nothing from `ray_tpu`.
Attention runs one key/value head's group of query heads at a time and a
block of queries at a time (`qblock`), so that an 8.7k-token sequence
fits beside a resident model.

What is ASSUMED of the architecture (the configuration's file lists each
with its reason) is written here as the reference does it:
- the attention layers rotate nothing;
- the router and the shared expert read the `hidden_size`-wide input,
  `W_in` comes before and `W_out` after the experts' weighted sum;
- the gated norm is `RMSNorm(y * silu(z))` over groups of `d_inner /
  n_groups` with one gain a channel;
- `dt` is not clamped after its softplus;
- the multi-token-prediction module is left out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.deepseek_v3 import (  # noqa: F401  (re-exported)
    F32, _identity, _mm, embed, head, margins, rms_norm, route)
from benchmarks.reference.dots3 import _blocks

MAMBA, ATTN, MOE = "M", "*", "E"


def relu2(h, up, down, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(h, up, quant))), down, quant)


def conv_shifted(x, w, b):
    """x [T, C], w [taps, C] (the last tap the token itself), b [C]: the
    causal depthwise convolution as `taps` shifted products."""
    T, taps = x.shape[0], w.shape[0]
    past = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return b[None] + sum(past[d:d + T] * w[d][None] for d in range(taps))


def recurrence(x, dt, A, B, C, keep=None):
    """x [T, H, P], dt [T, H], A [H], B / C [T, H, N] -> y [T, H, P]: one
    token at a time from a zero state.  `keep` (a count of tokens, may be
    traced): `(y, S [H, P, N])`, the state as it stands after that many
    tokens, set aside on the way."""
    def token(S, inputs):
        xt, dtt, Bt, Ct = inputs
        S = (jnp.exp(dtt * A)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :])
        return S, jnp.sum(S * Ct[:, None, :], axis=-1)

    S0 = jnp.zeros(x.shape[1:] + (B.shape[-1],), F32)
    if keep is None:
        return jax.lax.scan(token, S0, (x, dt, B, C))[1]

    def keeping(carry, inputs):
        S, y = token(carry[0], inputs[1:])
        return (S, jnp.where(inputs[0] == keep - 1, S, carry[1])), y

    (_, kept), y = jax.lax.scan(
        keeping, (S0, S0), (jnp.arange(x.shape[0]), x, dt, B, C))
    return y, kept


def mamba(h, w, *, heads, head_dim, groups, state, eps, quant, keep=None):
    """h [T, D] normed -> the mixer's output [T, D].  `keep` (a count of
    tokens): `(output, {"ssm", "conv"})`, what a server's slot holds
    after that many tokens: the recurrent state `[heads, head_dim,
    state]` and the convolution's last `taps - 1` inputs `[taps - 1,
    channels]`."""
    T, di, gn = h.shape[0], heads * head_dim, groups * state
    zxd = _mm(h, w["in_proj"], quant)
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * gn], zxd[:, 2 * di + 2 * gn:]
    taps = w["conv_w"].shape[0]
    held = None if keep is None else {"conv": jax.lax.dynamic_slice_in_dim(
        jnp.pad(xbc, ((taps - 1, 0), (0, 0))), keep, taps - 1, 0)}
    xbc = jax.nn.silu(conv_shifted(xbc, w["conv_w"], w["conv_b"]))
    x = xbc[:, :di].reshape(T, heads, head_dim)
    per_head = lambda t: jnp.repeat(  # noqa: E731
        t.reshape(T, groups, state), heads // groups, axis=1)
    y = recurrence(x, jax.nn.softplus(dt + w["dt_bias"][None]),
                   -jnp.exp(w["A_log"]), per_head(xbc[:, di:di + gn]),
                   per_head(xbc[:, di + gn:]), keep)
    if keep is not None:
        y, held["ssm"] = y
    y = y + w["D"][None, :, None] * x
    g = (y.reshape(T, di) * jax.nn.silu(z)).reshape(T, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    out = _mm(g.reshape(T, di) * w["gate_norm"][None], w["out_proj"], quant)
    return out if keep is None else (out, held)


def attention(h, w, *, heads, kv, hd, quant, qblock):
    """h [T, D] normed -> [T, D]: causal, unrotated, no cache."""
    T, G = h.shape[0], heads // kv
    qkv = _mm(h, w["wqkv"], quant)
    q = qkv[:, :heads * hd].reshape(T, kv, G, hd)
    k = qkv[:, heads * hd:(heads + kv) * hd].reshape(T, kv, hd)
    v = qkv[:, (heads + kv) * hd:].reshape(T, kv, hd)
    col = jnp.arange(T)

    def group(args):
        qg, kg, vg = args       # [T, G, hd], [T, hd], [T, hd]

        def block(t0):
            qb = jax.lax.dynamic_slice_in_dim(qg, t0, qblock, 0)
            seen = (t0 + jnp.arange(qblock))[:, None] >= col[None, :]
            a = jnp.einsum("qgd,kd->gqk", qb, kg, precision="highest")
            p = jax.nn.softmax(
                jnp.where(seen[None], a / math.sqrt(hd), -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, vg, precision="highest")

        return _blocks(block, T, qblock)                 # [T, G, hd]

    o = jax.lax.map(group, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                            jnp.moveaxis(v, 1, 0)))      # [kv, T, G, hd]
    return _mm(jnp.moveaxis(o, 0, 1).reshape(T, heads * hd), w["wo"], quant)


def routed(h, w, *, top_k, scale, offset, quant):
    """The HELD experts' part of the routed sum THROUGH `W_out`: the
    router scores all of its experts on `h` and the top-k is taken over
    all of them; expert `e` of the stacks is the router's expert `offset
    + e`, and works on `u = h W_in`."""
    weights, idx = route(h, w["router"].astype(F32),
                         w["router_bias"].astype(F32), top_k, scale, quant)
    u = _mm(h, w["w_in"].astype(F32), quant)

    def one(y, inputs):
        e, up, down = inputs
        coef = jnp.sum(jnp.where(idx == offset + e, weights, 0.0), axis=-1)
        return y + coef[:, None] * relu2(u, up.astype(F32), down.astype(F32),
                                         quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(w["e_up"].shape[0]), w["e_up"], w["e_down"]))
    return _mm(y, w["w_out"].astype(F32), quant)


def shared(h, w, quant=_identity):
    return relu2(h, w["s_up"].astype(F32), w["s_down"].astype(F32), quant)


def layer(x, w, *, kind, eps, mamba_kw, attn_kw, moe_kw, quant=_identity,
          qblock=128, keep=None):
    """x [T, D] float32 -> x [T, D]; `w` one layer's weights, any dtype;
    `kind` its mixer.  `keep` (a count of tokens): `(x, held)`, `held`
    what a Mamba layer holds after that many tokens (`mamba`), None for
    the other kinds."""
    small = {k: v.astype(F32) for k, v in w.items()
             if not k.startswith("e_")}
    h = rms_norm(x, small["norm"], eps)
    held = None
    if kind == MAMBA:
        out = mamba(h, small, eps=eps, quant=quant, keep=keep, **mamba_kw)
        if keep is not None:
            out, held = out
    elif kind == ATTN:
        out = attention(h, small, quant=quant,
                        qblock=min(qblock, x.shape[0]), **attn_kw)
    else:
        out = routed(h, {**w, **small}, quant=quant, **moe_kw) + shared(
            h, small, quant)
    return x + out if keep is None else (x + out, held)


def layer_kwargs(model: dict, l: int, offset: int = 0) -> dict:
    """`layer`'s keywords for layer `l` from the published config's keys."""
    return dict(
        kind=model["hybrid_override_pattern"][l],
        eps=model["layer_norm_epsilon"],
        mamba_kw=dict(heads=model["mamba_num_heads"],
                      head_dim=model["mamba_head_dim"],
                      groups=model["n_groups"],
                      state=model["ssm_state_size"]),
        attn_kw=dict(heads=model["num_attention_heads"],
                     kv=model["num_key_value_heads"], hd=model["head_dim"]),
        moe_kw=dict(top_k=model["num_experts_per_tok"],
                    scale=float(model["routed_scaling_factor"]),
                    offset=offset))
