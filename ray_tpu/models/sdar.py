"""SDAR-MoE decoder for SERVING: the Qwen3-MoE block under BLOCK
DIFFUSION.  The model does not write one token after the other: it
fills a BLOCK of `B` positions at a time, starting from a block of mask
tokens and deciding the positions it is surest of first, over a few
denoising forwards, each of which sees the whole block.

The architecture of `model_type: sdar_moe` checkpoints (JetLM,
`SDAR-30B-A3B-Chat`; the published `config.json` and the family's
public `generate.py`).  A layer, on `x [T, D]`, every layer the same:

    h = x + Wo attn(q, k, v);      y = h + moe(RMSNorm(h))

- `q, k, v = RMSNorm(x) Wq, Wk, Wv` as `H` / `KV` / `KV` heads, no bias;
  an RMS norm over the `head_dim` values of every q and k head (gains
  `q_norm`, `k_norm`) BEFORE the rotary; rotary over the whole head
  (halves), base `rope_theta`, no scaling; softmax attention in float32
  at scale `head_dim ** -0.5`, query head `h` through KV head `h // (H
  // KV)`.  The mask is BLOCK-CAUSAL: position `i` sees `j` iff `j // B
  <= i // B`: all of its own block and every block before.
- `moe`: `p = softmax(h W_g)` over all experts in float32, the `top_k`
  largest, their weights divided by their sum (`norm_topk_prob`), each
  expert a SwiGLU, none shared, no bias, no scale
  (`parallel/moe.dropless_moe` with `softmax_topk_route`).
- after the last layer `RMSNorm(.; final_norm)`, then the UNTIED head.  A
  position predicts ITS OWN token (no shift).

GENERATION (greedy, `remasking_strategy: low_confidence_dynamic`).  A
prompt's whole blocks are prefilled under the block-causal mask and
cached; its `T mod B` tail opens the first generated block as decided
positions.  A block at `pos`: tokens `blk [B]`, `und [B]` which are
undecided, step `s`.  One FORWARD runs `where(und, mask_id, blk)` at
positions `pos .. pos + B - 1`, writes its K and V rows there (again at
every forward of the block) and attends columns `0 .. pos + B - 1`;
`unmask` then decides positions: `n_s = B // S + (s < B mod S)` of
them, or every undecided position whose confidence `max softmax` is
over the threshold where those are at least `n_s`.  The forward that
decides the block's last position OUTPUTS it, and the next block starts
undecided.  What the cache owes the next block are the rows a forward
of the block's DECIDED tokens writes (the COMMIT; every forward before
had masks in its input): they are written by the next block's first
forward itself, which carries the decided block as a commit half at
`pos - B .. pos - 1` beside its own (`block_step(commit=)`: the mask is
block-causal, so in each layer both halves' rows are written and then
both attend).  A block so takes at most `S` forwards, none of them a
commit's own; a request's last block is output and never committed
(nobody reads its rows: a cache that SHARED prefixes would have to
commit it first).

What the serve engine needs, and nothing else:

- `forward`: a PACKED row's prompts (`llama.Packed`) under `same prompt
  AND block-causal`, the K and V rows to cache; logits only where asked
  (admission needs none: the first block's forward makes them).
- `block_step`: one forward of every live row's block, and of the
  block before it where that is still owed its clean rows, through the
  paged pool (`ops/paged_attention`: `B` rows appended a slot, then the
  decode kernel on `B x H` query heads of one row at position `pos + B
  - 1`: there is no mask inside a block; one call of each a half) or
  its dense view.
- `unmask`: the denoising choice, from the logits.

K and V pools are FOLDED: `[L, num_blocks, block_size, KV * hd]`, a
token's heads side by side in one row.  `jax.named_scope`s `block_attn`,
`block_kv_write`, `moe_router`, `moe_routed`, `lm_head`, `unmask`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.lfm2 import _at, _attend, _qkv
from ray_tpu.models.llama import Packed, _apply, _embed, _rms_norm
from ray_tpu.ops import paged_attention as _pa
from ray_tpu.parallel.moe import dropless_moe, softmax_topk_route

F32 = jnp.float32
EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
F32_LEAVES = ("router",)


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936
    max_seq_len: int = 32768
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate: int = 768
    n_experts: int = 128
    top_k: int = 8
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    # generation: the family's `generate.py` (not `config.json`)
    block_length: int = 4
    mask_id: int = 151669
    denoising_steps: int = 4          # a request's default `S <= B`
    confidence_threshold: float = 0.9  # ... and its default threshold
    dtype: Any = jnp.bfloat16
    attention: str = "dense"          # what the engine's packed prefill asks

    @staticmethod
    def from_hf(m: Dict, **generation) -> "SdarMoeConfig":
        """From the published `config.json`'s keys; `generation`: the
        fields `generate.py` holds (`block_length`, `mask_id`, ...) and
        `dtype`."""
        if m["mlp_only_layers"] or m["decoder_sparse_step"] != 1:
            raise ValueError("every layer of an sdar_moe is an expert layer")
        if m.get("use_sliding_window") or m.get("rope_scaling"):
            raise ValueError("sdar_moe: full attention, unscaled rotary")
        return SdarMoeConfig(
            vocab_size=m["vocab_size"],
            max_seq_len=m["max_position_embeddings"], dim=m["hidden_size"],
            n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            moe_intermediate=m["moe_intermediate_size"],
            n_experts=m["num_experts"], top_k=m["num_experts_per_tok"],
            norm_topk_prob=bool(m["norm_topk_prob"]),
            rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
            **generation)

    @staticmethod
    def tiny(vocab_size: int = 256, block_length: int = 4) -> "SdarMoeConfig":
        return SdarMoeConfig(
            vocab_size=vocab_size, max_seq_len=128, dim=64, n_layers=3,
            n_heads=4, n_kv_heads=2, head_dim=16, moe_intermediate=32,
            n_experts=8, top_k=2, block_length=block_length,
            mask_id=vocab_size - 1, denoising_steps=block_length,
            dtype=jnp.float32)


def layer_shapes(cfg: SdarMoeConfig) -> Dict[str, tuple]:
    D, H, KV, d = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, I = cfg.n_experts, cfg.moe_intermediate
    return {"attn_norm": (D,), "wq": (D, H * d), "wk": (D, KV * d),
            "wv": (D, KV * d), "q_norm": (d,), "k_norm": (d,),
            "wo": (H * d, D), "mlp_norm": (D,), "router": (D, E),
            "e_gate": (E, D, I), "e_up": (E, D, I), "e_down": (E, I, D)}


def init_params(cfg: SdarMoeConfig, key: jax.Array, std: float = 0.02):
    """Random weights in the tree the functions below read: `tok_emb`,
    `final_norm`, `lm_head` (untied) and `layers`, each leaf `[L, ...]`;
    the router float32 whatever the compute dtype."""
    def normal(k, shape, dt):
        return (jax.random.normal(k, shape, F32) * std).astype(dt)

    out = {"tok_emb": normal(jax.random.fold_in(key, 99),
                             (cfg.vocab_size, cfg.dim), cfg.dtype),
           "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
           "lm_head": normal(jax.random.fold_in(key, 98),
                             (cfg.dim, cfg.vocab_size), cfg.dtype)}
    layers = {}
    for i, (name, shape) in enumerate(sorted(layer_shapes(cfg).items())):
        dt = F32 if name in F32_LEAVES else cfg.dtype
        full = (cfg.n_layers,) + shape
        layers[name] = (jnp.ones(full, dt) if name.endswith("norm")
                        else normal(jax.random.fold_in(key, i), full, dt))
    out["layers"] = layers
    return out


# ----------------------------------------------------------------------
# parts
# ----------------------------------------------------------------------
def _rope_pos(x, theta: float, pos):
    """Rotary embedding (halves) at positions of the caller's own: x
    [..., T, heads, hd], pos [..., T]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    ang = pos.astype(F32)[..., None, None] * freqs      # [..., T, 1, half]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _route(cfg):
    return lambda h, layer, top_k, _scale, _eps: softmax_topk_route(
        h, layer["router"], top_k, cfg.norm_topk_prob)


def _moe(cfg, params, li, x, *, kernel, interpret, row_mask):
    """A layer's second half, pre-norm and residual: x [..., D] -> (y,
    the routed layer's stats)."""
    layer = _at(params["layers"], li)
    h = _rms_norm(x, layer["mlp_norm"].astype(cfg.dtype), cfg.norm_eps)
    experts = {k: params["layers"][k] for k in EXPERT_LEAVES}
    y, stats = dropless_moe(
        h.reshape(-1, h.shape[-1]), {**layer, **experts}, top_k=cfg.top_k,
        scale=1.0, route_eps=0.0, dtype=cfg.dtype, kernel=kernel,
        interpret=interpret, stack_index=li, row_mask=row_mask,
        route=_route(cfg))
    return x + y.reshape(h.shape), stats


def _head(cfg, params, x):
    """The untied head: x [..., D] -> float32 logits [..., vocab]."""
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("...d,dv->...v", x,
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=F32)


def block_mask(pos, block: int, seg=None):
    """[T, T] bool: row `i` sees column `j` iff `j`'s block is not after
    `i`'s (`pos` [T]: a token's position inside its own prompt) and,
    with `seg` [T], both lie in one prompt.  `block` 1 is the causal
    mask."""
    m = (pos[None, :] // block) <= (pos[:, None] // block)
    if seg is not None:
        m = m & (seg[:, None] == seg[None, :])
    return m


# ----------------------------------------------------------------------
# prefill: whole blocks under the block-causal mask
# ----------------------------------------------------------------------
def forward(cfg: SdarMoeConfig, params: Dict, tokens: jax.Array, *,
            packed: Optional[Packed] = None, logits: bool = True,
            block: Optional[int] = None, kernel: bool = False,
            interpret: bool = False):
    """tokens [B, T] -> (logits float32 or None, (ks, vs)).

    `packed` None: every row one sequence from position 0, logits `[B,
    T, vocab]`.  `packed` (B == 1, `seg` and `pos`): the row holds
    several prompts end to end; a token attends inside its own prompt
    only, a padding token (`seg` < 0) is routed to no expert, and the
    logits are `[1, K, vocab]`, the rows `packed.last`.  `logits` False:
    the head is not run (admission: no position of a prompt yields a
    token).  `block`: the mask's block length (the config's; 1 is the
    causal mask).  `ks`, `vs` `[L, B, T, KV * hd]`: the rows to cache,
    as the pool folds them."""
    B, T = tokens.shape
    block = cfg.block_length if block is None else block
    segmented = packed is not None and packed.seg is not None
    if segmented:
        if B != 1:
            raise ValueError("a packed prefill takes one row")
        pos, real = packed.pos, packed.seg >= 0
        mask = block_mask(pos, block, packed.seg)
    else:
        pos, real = jnp.arange(T, dtype=jnp.int32), None
        mask = block_mask(pos, block)
    x = _embed(params, tokens, cfg.dtype).astype(cfg.dtype)

    def layer(x, li):
        w = _at(params["layers"], li)
        h = _rms_norm(x, w["attn_norm"].astype(cfg.dtype), cfg.norm_eps)
        with jax.named_scope("block_attn"):
            q, k, v = _qkv(cfg, w, h,
                           lambda t: _rope_pos(t, cfg.rope_theta, pos))
            x = x + _apply(_attend(cfg, q, k, v, mask), w["wo"], cfg.dtype)
        x, _ = _moe(cfg, params, li, x, kernel=kernel, interpret=interpret,
                    row_mask=real)
        return x, (k.reshape(B, T, -1), v.reshape(B, T, -1))

    x, kv = lax.scan(layer, x, jnp.arange(cfg.n_layers, dtype=jnp.int32))
    if not logits:
        return None, kv
    if packed is not None:
        x = x[:, packed.last]
    return _head(cfg, params, x), kv


# ----------------------------------------------------------------------
# generation: one forward of every live row's block
# ----------------------------------------------------------------------
def block_step(cfg: SdarMoeConfig, params: Dict, tokens: jax.Array, cache,
               pos, *, tables=None, live=None, commit=None,
               kernel: bool = False, interpret: bool = False):
    """One forward of a block a row: tokens [S, B] (masks where a
    position is undecided) at positions `pos[s] .. pos[s] + B - 1`, `pos`
    [S] multiples of `B`; `cache` = `(k, v)`.  `tables` [S, W] given: the
    paged pools `[L, NB, BS, KV * hd]`; the block's `B` rows are written
    in place (`paged_kv_append(rows=B)`: ONE call, 0.35 ms a forward of
    six layers at the cell's shapes where `B` calls of one row take
    1.22; PERF.md section 3, PR 55) and the `B x H` queries of a row
    attend columns `0 .. pos + B - 1` through the decode kernel as
    `KV x (B x G)` heads of one query.  `tables` None: the dense view
    `[L, S, M, KV * hd]`, written by a slice update, attended under a
    mask.  Every forward of a block writes the block's rows AGAIN, from
    an input with masks until the block is decided: the rows that STAY
    are those a clean input wrote, which is what `commit` is for.
    Returns (logits [S, B, vocab] float32, cache, stats) with `stats` =
    `experts_touched`, `load_max` over the layers.

    `live` [S] bool (the engine's `pos < stop`; None: every row): a row
    that is not live writes nothing, attends nothing on the paged route
    and is routed to no expert.

    `commit` = `(clean [S, B], riding [S] bool)`: the forward carries a
    COMMIT HALF beside every row's block, the block BEFORE it with its
    decided tokens `clean`, at positions `pos - B .. pos - 1`, through
    the same layers: `2 S` rows of `B` positions.  The mask is
    block-causal, so in each layer both halves' rows are written first
    and then both attend, the commit half columns `0 .. pos - 1`: every
    row's result is what a forward of the clean block followed by a
    forward of the open one gives.  A half that does not ride (`riding`
    False) is a dead row.  On the paged route the halves are TWO calls
    of each kernel a layer, each over `S` rows (the shape a trace finds
    the attention by; a dead half's pages are not copied); in the view
    both blocks go into the slot's one row.  Only the open halves reach
    the head: the logits stay `[S, B, vocab]`.  `stats` count both
    halves' experts."""
    S, B = tokens.shape
    H, KV, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KV
    k_cache = cache[0]
    # the halves a slot: the open block at `pos`, the pending one behind
    halves = [(pos, live)]
    if commit is not None:
        tokens = jnp.concatenate([tokens, commit[0]])
        halves = [(pos, jnp.ones((S,), bool) if live is None else live),
                  (pos - B, commit[1])]
    parts = [slice(i * S, (i + 1) * S) for i in range(len(halves))]
    at = jnp.concatenate([p[:, None] + jnp.arange(B, dtype=pos.dtype)[None, :]
                          for p, _ in halves])                 # [n S, B]
    if tables is None:
        cols = jnp.arange(k_cache.shape[2])[None, :]
        valid = [(cols < (p + B)[:, None])[:, None, None, None, :]
                 for p, _ in halves]
    else:  # where a half's block goes, how far its queries attend
        spots = [_pa.dead_row_positions(p, on, tables, k_cache.shape[2])
                 for p, on in halves]
        spots = [(w, jnp.where(a < 0, a, a + B - 1)) for w, a in spots]
    rows = None if halves[0][1] is None else jnp.repeat(
        jnp.concatenate([on for _, on in halves]), B)
    x = _embed(params, tokens, cfg.dtype).astype(cfg.dtype)   # [n S, B, D]

    def write_view(c_rows, new, p, on):
        """The dense view's rows of a layer with each live half's block
        at its position: a slice update a row."""
        def one(c_row, n_row, p, on):
            p = jnp.maximum(p, 0)   # a first block has none behind it
            was = lax.dynamic_slice_in_dim(c_row, p, B, 0)
            return lax.dynamic_update_slice_in_dim(
                c_row, jnp.where(on, n_row.astype(c_row.dtype), was), p, 0)

        on = jnp.ones((S,), bool) if on is None else on
        return jax.vmap(one)(c_rows, new, p, on)

    def layer(carry, li):
        x, kc, vc = carry
        w = _at(params["layers"], li)
        h = _rms_norm(x, w["attn_norm"].astype(cfg.dtype), cfg.norm_eps)
        q, k, v = _qkv(cfg, w, h, lambda t: _rope_pos(t, cfg.rope_theta, at))
        k, v = k.reshape(-1, B, KV * d), v.reshape(-1, B, KV * d)
        if tables is not None:
            with jax.named_scope("block_kv_write"):
                for part, (w_pos, _) in zip(parts, spots):
                    kc, vc = _pa.paged_kv_append(
                        kc, vc, k[part].astype(kc.dtype),
                        v[part].astype(vc.dtype), tables, w_pos, li,
                        interpret=interpret, rows=B)
            with jax.named_scope("block_attn"):
                # KV x (B x G) query heads of ONE row: head `kv * B * G +
                # b * G + g` reads kv head `kv`, and every column up to
                # the block's last is its to see
                qh = q.reshape(-1, B, KV, G, d).transpose(0, 2, 1, 3, 4)
                qh = qh.reshape(-1, KV * B * G, d)
                o = jnp.concatenate([_pa.paged_decode_attention(
                    qh[part], kc, vc, tables, a_pos, li, interpret=interpret)
                    for part, (_, a_pos) in zip(parts, spots)])
                o = o.reshape(-1, KV, B, G, d).transpose(0, 2, 1, 3, 4)
                o = o.reshape(-1, B, H * d)
        else:
            with jax.named_scope("block_kv_write"):
                kr = lax.dynamic_index_in_dim(kc, li, 0, keepdims=False)
                vr = lax.dynamic_index_in_dim(vc, li, 0, keepdims=False)
                for part, (p, on) in zip(parts, halves):
                    kr = write_view(kr, k[part], p, on)
                    vr = write_view(vr, v[part], p, on)
                kc = lax.dynamic_update_index_in_dim(kc, kr, li, 0)
                vc = lax.dynamic_update_index_in_dim(vc, vr, li, 0)
            with jax.named_scope("block_attn"):
                o = jnp.concatenate([_attend(
                    cfg, q[part], kr.reshape(S, -1, KV, d),
                    vr.reshape(S, -1, KV, d), ok)
                    for part, ok in zip(parts, valid)])
        x = x + _apply(o.astype(cfg.dtype), w["wo"], cfg.dtype)
        x, stats = _moe(cfg, params, li, x, kernel=kernel,
                        interpret=interpret, row_mask=rows)
        return (x, kc, vc), (stats["experts_touched"], stats["load_max"])

    (x, kc, vc), (touched, load) = lax.scan(
        layer, (x, *cache), jnp.arange(cfg.n_layers, dtype=jnp.int32))
    return _head(cfg, params, x[:S]), (kc, vc), {
        "experts_touched": jnp.sum(touched), "load_max": jnp.max(load)}


def unmask(logits, blk, und, dec, s, steps, thr):
    """The denoising choice of `low_confidence_dynamic`, a row a block:
    logits [S, B, vocab] float32 of a forward whose input had masks at
    `und` [S, B]; `blk` [S, B] the block's tokens, `dec` [S, B] the step
    each position was decided at, `s` [S] the block's step, `steps` [S]
    the request's `S`, `thr` [S] its confidence threshold.  `x0 =
    argmax`, confidence `c = max softmax` (float32, temperature 1); `n_s
    = B // S + (s < B mod S)`; the undecided positions over the
    threshold if they are at least `n_s`, else the `n_s` surest
    undecided positions (ties to the lower position).  -> (blk, und,
    dec) after the choice."""
    B = blk.shape[-1]
    with jax.named_scope("unmask"):
        top = jnp.max(logits, axis=-1)
        x0 = jnp.argmax(logits, axis=-1).astype(blk.dtype)
        c = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
        steps = jnp.maximum(steps, 1)   # a slot never used holds 0
        n_s = (B // steps + (s < B % steps))[:, None]
        sure = und & (c > thr[:, None])
        cu = jnp.where(und, c, -1.0)
        i = jnp.arange(B)
        # position j goes before i: surer, or as sure and lower
        before = ((cu[:, None, :] > cu[:, :, None])
                  | ((cu[:, None, :] == cu[:, :, None])
                     & (i[None, None, :] < i[None, :, None])))
        rank = jnp.sum(before & und[:, None, :], axis=-1)
        chosen = jnp.where(jnp.sum(sure, axis=-1, keepdims=True) >= n_s,
                           sure, und & (rank < n_s))
        return (jnp.where(chosen, x0, blk), und & ~chosen,
                jnp.where(chosen, s[:, None].astype(dec.dtype), dec))
