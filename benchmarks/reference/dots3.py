"""Plain reference for the decoder layer of `dots-studio/dots3-note-prev`
(`config.json`, `model_type: dots3_note`): pre-norm RMSNorm; latent
attention in its EXPANDED form only (the compressed KV goes through
`W_kvb` to per-head keys and values; nothing is absorbed, nothing is
cached) with a query rank, the two rescaling scalars on the normalised
latents, and a head-wise sigmoid gate; in a FULL layer the learned
selection as an explicit top-k over this reference's OWN float32 index
scores, turned into a mask; in a WINDOW layer the window as a mask;
then a dense SwiGLU or the expert layer: sigmoid router with a
correction bias over ALL the router's experts, top-k of `scores +
bias`, the experts one at a time, of which only those this chip HOLDS
exist (`offset`: the first held expert; a pair routed elsewhere adds
nothing, as in the deployment, whose other chips add their parts), plus
the shared expert.  `jax.numpy`, float32, matmul precision `highest`;
no cache, no kernels, no sorting, no gathering of selected rows;
nothing from `ray_tpu`.

Computed in BLOCKS so that a 16.8k-token sequence fits beside a
resident model: attention runs a group of heads at a time (`hgroup`),
inside it a block of queries at a time (`qblock`) against all keys
under the mask; the selection's mask `[T, T]` is made once a layer, a
block of queries at a time.

What is ASSUMED of the architecture (the configuration's file lists
each with its source) is written here as the reference does it:
- `apply_mla_qkv_lora_rescale`: `c_q` and `c_kv` times `sqrt(hidden /
  rank)` after their RMSNorm, the rotary key unscaled (LongCat-Flash,
  arXiv:2509.01322);
- the gate: `sigmoid(h W_g)`, one value a head, on the head's output
  before `W_o` (arXiv:2505.06708), `h` the normed input;
- the indexer as DeepSeek-V3.2-Exp's: `I[t, s] = sum_j w[t, j] relu(qI[t,
  j] . kI[s])`, `kI` a LayerNorm (gain and bias) of `h W_kI`, rotary on
  the first `rope` dims of `qI` and `kI`; no Hadamard rotation (it
  leaves `q . k` unchanged), no fp8 of the keys;
- the window counts the token itself: `0 <= t - s < window`;
- rotary rotates the pairs (2i, 2i + 1) in place (the program
  de-interleaves queries and keys alike: the same scores).

`layer` also reports `index_select_overlap`: over the query rows the
caller names, the share of the set a selection on BFLOAT16-rounded
index operands picks (how the program scores: bfloat16 in, float32
accumulation) that the float32 selection picked too.  Sets differ at
the `index_topk`-th place, where scores are close.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.deepseek_v3 import (  # noqa: F401  (re-exported)
    F32, _identity, _mm, embed, head, margins, rms_norm, rope_pairs, route,
    swiglu)

FULL, SWA = "full_attention", "sliding_attention"


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _rope_head(x, n, theta):
    """Rotary on the first `n` dims of x [T, ..., d]."""
    return jnp.concatenate([rope_pairs(x[..., :n], theta), x[..., n:]], -1)


def _blocks(fn, T, block):
    """`fn(first row of the block)` over `T // block` blocks of rows,
    one after the other; the results stacked back to `[T, ...]`."""
    out = jax.lax.map(fn, jnp.arange(0, T, block))
    return out.reshape((T,) + out.shape[2:])


def index_scores(qI, wI, kI):
    """[Q, Hi, di], [Q, Hi], [T, di] -> I [Q, T]."""
    s = jnp.einsum("qjd,kd->qjk", qI, kI, precision="highest")
    return jnp.sum(jax.nn.relu(s) * wI[..., None], axis=1)


def selection_mask(qI, wI, kI, topk, qblock):
    """mask [T, T]: row t is True at the `topk` largest `I[t, s]` over
    `s <= t` (at every `s <= t` while there are no more)."""
    T = kI.shape[0]

    def block(t0):
        q = jax.lax.dynamic_slice_in_dim(qI, t0, qblock, 0)
        w = jax.lax.dynamic_slice_in_dim(wI, t0, qblock, 0)
        return _chosen(index_scores(q, w, kI), t0, topk)

    return _blocks(block, T, qblock)


def _chosen(scores, t0, topk):
    """scores [Q, T] of the queries at positions `t0 ..`: True at each
    row's `topk` largest over `s <= t` (equal scores: the earlier key
    first, as `lax.top_k` orders them; at tiny widths a key every index
    head scores below zero reads exactly 0, and several may)."""
    Q, T = scores.shape
    causal = jnp.arange(T)[None, :] <= (t0 + jnp.arange(Q))[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, T))
    picked = jnp.zeros((Q, T), bool).at[jnp.arange(Q)[:, None], idx].set(True)
    return causal & picked


def window_mask(T, window):
    d = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    return (d >= 0) & (d < window)


def attention(h, w, mask, *, heads, nope, rope, v_dim, rank, theta, eps,
              s_q, s_kv, quant, qblock, hgroup):
    """h [T, D] normed, mask [T, T] -> the gated heads' outputs [T,
    heads * v_dim]."""
    T = h.shape[0]
    G = heads // hgroup
    c_q = s_q * rms_norm(_mm(h, w["wq_a"], quant), w["q_norm"], eps)
    kv_a = _mm(h, w["wkv_a"], quant)
    c = s_kv * rms_norm(kv_a[:, :rank], w["kv_norm"], eps)
    k_rope = rope_pairs(kv_a[:, rank:], theta)          # one a token
    gate = jax.nn.sigmoid(_mm(h, w["w_gate_attn"], quant))   # [T, heads]
    # a group of heads at a time: its columns of W_qb and W_kvb
    wq_b = w["wq_b"].reshape(-1, G, hgroup * (nope + rope))
    wkv_b = w["wkv_b"].reshape(rank, G, hgroup * (nope + v_dim))
    scale = 1.0 / math.sqrt(nope + rope)

    def group(ws):
        wq, wkv = ws
        q = _mm(c_q, wq, quant).reshape(T, hgroup, nope + rope)
        q = jnp.concatenate(
            [q[..., :nope], rope_pairs(q[..., nope:], theta)], -1)
        kv = _mm(c, wkv, quant).reshape(T, hgroup, nope + v_dim)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, None, :], (T, hgroup, rope))], -1)
        v = kv[..., nope:]

        def block(t0):
            qb = jax.lax.dynamic_slice_in_dim(q, t0, qblock, 0)
            mb = jax.lax.dynamic_slice_in_dim(mask, t0, qblock, 0)
            s = jnp.einsum("qhd,khd->hqk", qb, k, precision="highest")
            p = jax.nn.softmax(jnp.where(mb[None], s * scale, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", p, v, precision="highest")

        return _blocks(block, T, qblock)                 # [T, hgroup, v]

    o = jax.lax.map(group, (jnp.moveaxis(wq_b, 1, 0),
                            jnp.moveaxis(wkv_b, 1, 0)))  # [G, T, hgroup, v]
    o = jnp.moveaxis(o, 0, 1).reshape(T, heads, v_dim)
    return (o * gate[..., None]).reshape(T, heads * v_dim)


def routed(h, w, *, top_k, scale, offset, quant):
    """The HELD experts' part of the routed sum: the router scores all
    of its experts and the top-k is taken over all of them; expert `e`
    of the stacks is the router's expert `offset + e`; a pair routed to
    an expert outside the stacks adds nothing."""
    weights, idx = route(h, w["router"].astype(F32),
                         w["router_bias"].astype(F32), top_k, scale, quant)

    def one(y, inputs):
        e, gate, up, down = inputs
        coef = jnp.sum(jnp.where(idx == offset + e, weights, 0.0), axis=-1)
        out = swiglu(h, gate.astype(F32), up.astype(F32), down.astype(F32),
                     quant)
        return y + coef[:, None] * out, None

    E = w["e_gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(E), w["e_gate"], w["e_up"], w["e_down"]))
    return y


def shared(h, w, quant=_identity):
    return swiglu(h, w["s_gate"].astype(F32), w["s_up"].astype(F32),
                  w["s_down"].astype(F32), quant)


def layer(x, w, *, kind, attn, index=None, window=None, eps, top_k, scale,
          offset=0, quant=_identity, qblock=128, hgroup=16,
          overlap_rows=None):
    """x [T, D] float32 -> (x [T, D], index_select_overlap or nan).

    `w` one layer's weights, any dtype; `kind` its attention form;
    `attn` that form's widths (`attn_kwargs`); `index` = `(heads, dim,
    topk)` for a full layer, `window` for a window layer; `overlap_rows`
    = `(first, count)`: the query rows the overlap is taken over."""
    small = {k: v.astype(F32) for k, v in w.items()
             if not k.startswith("e_")}
    T = x.shape[0]
    qblock, hgroup = min(qblock, T), min(hgroup, attn["heads"])
    h = rms_norm(x, small["attn_norm"], eps)
    overlap = jnp.asarray(jnp.nan, F32)
    if kind == FULL:
        Hi, di, topk = index
        rope, theta = attn["rope"], attn["theta"]
        c_q = attn["s_q"] * rms_norm(_mm(h, small["wq_a"], quant),
                                     small["q_norm"], eps)
        qI = _rope_head(_mm(c_q, small["idx_wq"], quant).reshape(T, Hi, di),
                        rope, theta)
        kI = _rope_head(layer_norm(_mm(h, small["idx_wk"], quant),
                                   small["idx_k_norm"], small["idx_k_bias"],
                                   eps), rope, theta)
        wI = _mm(h, small["idx_ww"], quant) * (Hi ** -0.5 * di ** -0.5)
        mask = selection_mask(qI, wI, kI, topk, qblock)
        if overlap_rows is not None:
            t0, n = overlap_rows  # `t0` may be traced, `n` is static
            cut = lambda v: jax.lax.dynamic_slice_in_dim(v, t0, n, 0)  # noqa: E731
            low = selection_mask_rows(cut(qI), cut(wI), kI, t0, topk,
                                      jnp.bfloat16)
            overlap = (jnp.sum(low & cut(mask)) / jnp.sum(low)).astype(F32)
    else:
        mask = window_mask(T, window)
    o = attention(h, small, mask, eps=eps, quant=quant, qblock=qblock,
                  hgroup=hgroup, **attn)
    x = x + _mm(o, small["wo"], quant)
    h = rms_norm(x, small["mlp_norm"], eps)
    if "router" not in w:
        return x + swiglu(h, small["w_gate"], small["w_up"], small["w_down"],
                          quant), overlap
    y = routed(h, {**w, **small}, top_k=top_k, scale=scale, offset=offset,
               quant=quant)
    return x + y + shared(h, small, quant), overlap


def selection_mask_rows(qI, wI, kI, t0, topk, round_to):
    """`selection_mask`'s rows `t0 .. t0 + len(qI)` alone, the operands
    through `round_to` first (the scores still accumulate in float32)."""
    qI, wI, kI = (v.astype(round_to).astype(F32) for v in (qI, wI, kI))
    return _chosen(index_scores(qI, wI, kI), t0, topk)


def attn_kwargs(model: dict, kind: str) -> dict:
    """One attention form's widths from the published config's keys."""
    p = "" if kind == FULL else "swa_"
    D = model["hidden_size"]
    q_rank, rank = model[p + "q_lora_rank"], model[p + "kv_lora_rank"]
    on = model["apply_mla_qkv_lora_rescale"]
    return dict(
        heads=model[p + "num_attention_heads"],
        nope=model[p + "qk_nope_head_dim"], rope=model[p + "qk_rope_head_dim"],
        v_dim=model[p + "v_head_dim"], rank=rank,
        theta=float(model[p + "rope_theta"]),
        s_q=math.sqrt(D / q_rank) if on else 1.0,
        s_kv=math.sqrt(D / rank) if on else 1.0)


def layer_kwargs(model: dict, l: int, offset: int = 0) -> dict:
    """`layer`'s keywords for layer `l` from the published config's keys."""
    kind = model["layer_types"][l]
    kw = dict(kind=kind, attn=attn_kwargs(model, kind),
              eps=model["rms_norm_eps"], top_k=model["num_experts_per_tok"],
              scale=model["routed_scaling_factor"], offset=offset)
    if kind == FULL:
        kw["index"] = (model["index_n_heads"], model["index_head_dim"],
                       model["index_topk"])
    else:
        kw["window"] = model["sliding_window_size"]
    return kw
