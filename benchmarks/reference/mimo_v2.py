"""Plain reference for the decoder layer of `XiaomiMiMo/MiMo-V2.5`
(`config.json`, `model_type: mimo_v2`): pre-norm RMSNorm; grouped-query
attention from one fused projection `q | k | v = h W_qkv`, keys and
queries 192 wide and values 128; rotary on the FIRST `rot` dims of each
query and key head; `v` times `attention_value_scale`; scores `q k^T /
sqrt(192)` under a causal mask, in a WINDOW layer also `i - window < j`,
and there the SINK: the layer's scalar `s_h` a query head is appended to
the row's scores as one more column, the softmax runs over scores and
sink together, and the sink's probability is dropped; then a dense
SwiGLU or the expert layer: sigmoid router with a correction bias over
ALL the router's experts, top-k of `scores + bias`, the experts one at a
time, of which only those this chip HOLDS exist (`offset`: the first
held expert; a pair routed elsewhere adds nothing, as in the deployment,
whose other chips add their parts); no shared expert.  `jax.numpy`,
float32, matmul precision `highest`; no cache, no ring, no kernels, no
sorting; nothing from `ray_tpu`.

Computed in BLOCKS so that an 8.8k-token sequence fits beside a resident
model: attention runs one key/value head's group of query heads at a
time, inside it a block of queries at a time (`qblock`) against all keys
under the mask.

What is ASSUMED of the architecture (the configuration's file lists each
with its reason) is written here as the reference does it:
- rotary is the half-split form (`x1, x2 = x[:rot/2], x[rot/2:rot]`;
  `x1 cos - x2 sin | x1 sin + x2 cos`), `rot = int(192 * 0.334) = 64`,
  base `rope_theta` in a full layer and `swa_rope_theta` in a window
  layer, no scaling;
- the window counts the token itself: `0 <= i - j < window`;
- `hybrid_layer_pattern` 0 is a full layer, 1 a window layer;
- the router's normaliser is `sum + 1e-20` and `routed_scaling_factor`
  null is 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.deepseek_v3 import (  # noqa: F401  (re-exported)
    F32, _identity, _mm, embed, head, margins, rms_norm, swiglu)
from benchmarks.reference.dots3 import _blocks, routed

FULL, SWA = 0, 1


def rope_half(x, theta, rot):
    """x [T, heads, d]: the first `rot` dims rotated in the half-split
    form at positions `0 .. T`, the rest as they are."""
    T, half = x.shape[0], rot // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [a * cos - b * sin, a * sin + b * cos, x[..., rot:]], axis=-1)


def attention(h, w, *, heads, kv, dk, dv, rot, theta, value_scale, window,
              sink, quant, qblock):
    """h [T, D] normed -> the heads' outputs [T, heads * dv].  `window`
    None: a full layer; `sink`: the layer's `w["sink"]` [heads] joins
    the softmax."""
    T, G = h.shape[0], heads // kv
    qkv = _mm(h, w["wqkv"], quant)
    nq, nk = heads * dk, kv * dk
    q = rope_half(qkv[:, :nq].reshape(T, heads, dk), theta, rot)
    k = rope_half(qkv[:, nq:nq + nk].reshape(T, kv, dk), theta, rot)
    v = (qkv[:, nq + nk:] * value_scale).reshape(T, kv, dv)
    scale = 1.0 / math.sqrt(dk)
    col = jnp.arange(T)
    s_h = (w["sink"].reshape(kv, G) if sink
           else jnp.zeros((kv, G), F32))

    def group(args):
        qg, kg, vg, sg = args   # [T, G, dk], [T, dk], [T, dv], [G]

        def block(t0):
            qb = jax.lax.dynamic_slice_in_dim(qg, t0, qblock, 0)
            d = (t0 + jnp.arange(qblock))[:, None] - col[None, :]
            mask = d >= 0
            if window is not None:
                mask = mask & (d < window)
            a = jnp.einsum("qgd,kd->gqk", qb, kg, precision="highest")
            a = jnp.where(mask[None], a * scale, -jnp.inf)
            if sink:
                a = jnp.concatenate(
                    [a, jnp.broadcast_to(sg[:, None, None],
                                         a.shape[:2] + (1,))], axis=-1)
            p = jax.nn.softmax(a, axis=-1)[..., :T]
            return jnp.einsum("gqk,kd->qgd", p, vg, precision="highest")

        return _blocks(block, T, qblock)                 # [T, G, dv]

    o = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(T, kv, G, dk), 1, 0), jnp.moveaxis(k, 1, 0),
        jnp.moveaxis(v, 1, 0), s_h))                     # [kv, T, G, dv]
    return jnp.moveaxis(o, 0, 1).reshape(T, heads * dv)


def layer(x, w, *, attn, eps, top_k, scale, offset=0, quant=_identity,
          qblock=128):
    """x [T, D] float32 -> x [T, D]; `w` one layer's weights, any dtype;
    `attn` its attention's keywords (`attn_kwargs`)."""
    small = {k: v.astype(F32) for k, v in w.items()
             if not k.startswith("e_")}
    qblock = min(qblock, x.shape[0])
    h = rms_norm(x, small["attn_norm"], eps)
    o = attention(h, small, quant=quant, qblock=qblock, **attn)
    x = x + _mm(o, small["wo"], quant)
    h = rms_norm(x, small["mlp_norm"], eps)
    if "router" not in w:
        return x + swiglu(h, small["w_gate"], small["w_up"], small["w_down"],
                          quant)
    return x + routed(h, {**w, **small}, top_k=top_k, scale=scale,
                      offset=offset, quant=quant)


def attn_kwargs(model: dict, kind: int) -> dict:
    """One layer kind's attention from the published config's keys."""
    p = "" if kind == FULL else "swa_"
    window = kind == SWA
    return dict(
        heads=model[p + "num_attention_heads"],
        kv=model[p + "num_key_value_heads"], dk=model[p + "head_dim"],
        dv=model[p + "v_head_dim"],
        rot=int(model[p + "head_dim"] * model["partial_rotary_factor"]),
        theta=float(model["swa_rope_theta" if window else "rope_theta"]),
        value_scale=float(model["attention_value_scale"]),
        window=model["sliding_window"] if window else None,
        sink=bool(model["add_swa_attention_sink_bias" if window
                        else "add_full_attention_sink_bias"]))


def layer_kwargs(model: dict, l: int, offset: int = 0) -> dict:
    """`layer`'s keywords for layer `l` from the published config's keys."""
    return dict(attn=attn_kwargs(model, model["hybrid_layer_pattern"][l]),
                eps=model["layernorm_epsilon"],
                top_k=model["num_experts_per_tok"],
                scale=float(model["routed_scaling_factor"] or 1.0),
                offset=offset)
