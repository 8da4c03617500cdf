"""Plain reference for the `afmoe` family (Trinity-Mini's `config.json`
and the family's public modelling code, `modeling_afmoe.py`): `jax.numpy`,
float32, matmul precision `highest`, no kernels, nothing from `ray_tpu`.

    h0   = E[tokens] * sqrt(hidden)                          (mup_enabled)
    a    = rms(h; w_in)
    q, k, v = Wq a, Wk a, Wv a;  g = Wg a
    q, k = rms over each head's 128 (w_qn, w_kn)
    q, k = rotary, the two halves of a head against each other, in the
           SLIDING layers only; full layers use no positions
    o    = softmax(q k^T / sqrt(hd) + mask) v     causal; sliding: keys
           i - window < j <= i; a KV head serves H / KV query heads
    o    = o * sigmoid(g)
    h    = h + rms(Wo o; w_post_attn)
    m    = rms(h; w_pre_mlp)
    f    = Wd(silu(Wg' m) * Wu m)                            (dense layers)
    f    = shared(m) + sum over the top-k of (s + b) of w_e expert_e(m)
           s = sigmoid(Wr m);  w = s[picked] / (sum s[picked] + 1e-20) * scale
    h    = h + rms(f; w_post_mlp)
    loss = mean next-token cross entropy of Whead rms(h_L; w_f)

Departures from the published description, each because what is run is
ONE CHIP'S SHARE (the configuration's `deployment`):

- `held = (offset, count)`: only those experts' matrices exist here; a
  picked expert outside them adds nothing (the uncut layer is this
  function with `held = (0, num_experts)` and all the matrices);
- `vocab_slice = (offset, rows)`: the table and the head hold those rows
  of the vocabulary, the log-sum-exp runs over their logits, and a token
  outside embeds to zeros;
- the bias `b` is an input and takes no gradient; `bias_rule` is the
  balancing rule on its own.

How it is computed, which changes no value: every layer is a
`jax.checkpoint`, attention runs a block of query rows at a time (each
block a checkpoint), and a held expert is applied to EVERY row and
masked by whether the row picked it (no sort, no grouped product).
`quant` is the precision control's hook on every matmul operand;
`window_off` is the control that lets every layer see its whole prefix.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512


def _identity(x):
    return x


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision="highest")


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope_half_split(x, theta):
    """x [T, H, hd]."""
    T, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attend(q, k, v, window, quant):
    """q [T, H, hd], k / v [T, KV, hd] -> [T, H, hd]."""
    T, H, hd = q.shape
    KV = k.shape[1]
    block = min(QUERY_BLOCK, T)
    while T % block:
        block //= 2
    qg = q.reshape(T // block, block, KV, H // KV, hd)

    @jax.checkpoint
    def rows(args):
        qb, r0 = args
        s = jnp.einsum("qcgd,kcd->cgqk", quant(qb), quant(k),
                       precision="highest") / math.sqrt(hd)
        below = (r0 + jnp.arange(block))[:, None] - jnp.arange(T)[None, :]
        live = below >= 0
        if window is not None:
            live = live & (below < window)
        p = jax.nn.softmax(jnp.where(live[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("cgqk,kcd->qcgd", quant(p), quant(v),
                          precision="highest")

    out = jax.lax.map(rows, (qg, jnp.arange(T // block) * block))
    return out.reshape(T, H, hd)


def swiglu(m, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(m, gate, quant)) * _mm(m, up, quant), down,
               quant)


def route(m, router, bias, top_k, scale):
    """-> (weights [T, k], experts [T, k])."""
    s = jax.nn.sigmoid(jnp.matmul(m, router, precision="highest"))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale, idx


def routed(m, w, idx, layer, held, quant):
    """The held experts' part of the routed sum: expert `e` on every row,
    times the row's weight for it (0 where the row did not pick it)."""
    lo, count = held

    def one(y, e):
        mine = jnp.sum(jnp.where(idx == lo + e[0], w, 0.0), axis=-1)
        out = jax.checkpoint(lambda m, g, u, d: swiglu(m, g, u, d, quant))(
            m, e[1], e[2], e[3])
        return y + out * mine[:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        jnp.arange(count), layer["e_gate"], layer["e_up"], layer["e_down"]))
    return y


def layer_fn(x, layer, bias, kind, model, held, quant, window_off):
    """x [T, D] -> (x, counts [num_experts])."""
    T, D = x.shape
    H, KV, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    eps = model["rms_norm_eps"]
    sliding = kind == "sliding_attention"
    a = rms(x, layer["in_norm"], eps)
    q = rms(_mm(a, layer["wq"], quant).reshape(T, H, hd), layer["q_norm"], eps)
    k = rms(_mm(a, layer["wk"], quant).reshape(T, KV, hd), layer["k_norm"],
            eps)
    v = _mm(a, layer["wv"], quant).reshape(T, KV, hd)
    g = _mm(a, layer["w_gate_attn"], quant)
    if sliding:
        q = rope_half_split(q, model["rope_theta"])
        k = rope_half_split(k, model["rope_theta"])
    window = model["sliding_window"] if sliding and not window_off else None
    o = attend(q, k, v, window, quant).reshape(T, H * hd) * jax.nn.sigmoid(g)
    x = x + rms(_mm(o, layer["wo"], quant), layer["post_attn_norm"], eps)
    m = rms(x, layer["pre_mlp_norm"], eps)
    E = model["num_experts"]
    if "router" not in layer:
        f = swiglu(m, layer["w_gate"], layer["w_up"], layer["w_down"], quant)
        counts = jnp.zeros((E,), jnp.int32)
    else:
        w, idx = route(m, layer["router"], bias,
                       model["num_experts_per_tok"], model["route_scale"])
        f = (swiglu(m, layer["s_gate"], layer["s_up"], layer["s_down"], quant)
             + routed(m, w, idx, layer, held, quant))
        counts = jnp.sum(idx.reshape(-1, 1) == jnp.arange(E), axis=0,
                         dtype=jnp.int32)
    return x + rms(f, layer["post_mlp_norm"], eps), counts


def hidden_one(params, tokens, model, held, vocab_slice, router_bias,
               quant=_identity, window_off=False):
    """tokens [T] -> (final normed hidden states [T, D], counts [expert
    layers, num_experts])."""
    lo, rows = vocab_slice
    local = tokens - lo
    inside = (local >= 0) & (local < rows)
    x = jnp.where(inside[:, None],
                  params["embed"][jnp.clip(local, 0, rows - 1)], 0.0)
    if model["mup_enabled"]:
        x = x * math.sqrt(model["hidden_size"])
    counts = []
    n_dense = model["num_dense_layers"]
    for i, (layer, kind) in enumerate(zip(params["layers"],
                                          model["layer_types"])):
        bias = router_bias[i - n_dense] if i >= n_dense else None
        x, c = jax.checkpoint(
            lambda x, layer, bias, kind=kind: layer_fn(
                x, layer, bias, kind, model, held, quant, window_off))(
            x, layer, bias)
        if i >= n_dense:
            counts.append(c)
    return rms(x, params["norm"], model["rms_norm_eps"]), jnp.stack(counts)


def loss(params, tokens, model, held, vocab_slice, router_bias,
         quant=_identity, window_off=False):
    """tokens [B, T + 1] -> (mean next-token cross entropy over the
    slice, counts [expert layers, num_experts] summed over the batch)."""
    lo, rows = vocab_slice

    def one(seq):
        x, counts = hidden_one(params, seq[:-1], model, held, vocab_slice,
                               router_bias, quant, window_off)
        lg = _mm(x, params["head"], quant)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        local = seq[1:] - lo
        inside = (local >= 0) & (local < rows)
        tgt = jnp.take_along_axis(
            lg, jnp.clip(local, 0, rows - 1)[:, None], axis=-1)[:, 0]
        return lse - jnp.where(inside, tgt, 0.0), counts

    nll, counts = jax.lax.map(one, tokens)
    return jnp.mean(nll), jnp.sum(counts, axis=0)


def loss_and_grad(params, tokens, model, held, vocab_slice, router_bias,
                  quant=_identity, window_off=False):
    """-> ((loss, counts), the gradient by every leaf of `params`)."""
    return jax.value_and_grad(
        lambda p: loss(p, tokens, model, held, vocab_slice, router_bias,
                       quant, window_off), has_aux=True)(params)


def bias_rule(bias, counts, coeff):
    """The balancing rule, in numpy: `b += coeff * (d - mean(d))`, `d =
    sign(mean(c) - c)`, a layer a row."""
    c = np.asarray(counts, np.float32)
    d = np.sign(c.mean(axis=-1, keepdims=True) - c)
    return (np.asarray(bias, np.float32)
            + np.float32(coeff) * (d - d.mean(axis=-1, keepdims=True)))
