"""MiMo-V2-lineage decoder for SERVING: window layers with a learned sink
beside full layers of other head counts, and a share of a wide expert
layer.

The language model of `model_type: mimo_v2` checkpoints
(`XiaomiMiMo/MiMo-V2.5`, 309B-A15B; the published `config.json`).
Pre-norm RMSNorm, a residual around each half.  A layer's first half is
grouped-query attention from ONE fused projection (`q | k | v = h W_qkv`,
no bias), keys and queries `head_dim` (192) wide and values `v_head_dim`
(128), of one of two kinds (`hybrid_layer_pattern`):

- a FULL layer (0): `n_heads` query heads on `n_kv_heads` (4) key/value
  heads, rotary base `rope_theta`, causal over the whole context;
- a WINDOW layer (1): `swa_n_heads` on `swa_n_kv_heads` (8), rotary base
  `swa_rope_theta`, row `i` sees `j` with `i - window < j <= i`, and a
  SINK: one learned scalar a query head, `s_h`, joins the row's scores
  as one more column of the softmax whose probability is dropped, `p_ij
  = exp(a_ij - m) / (sum_j exp(a_ij - m) + exp(s_h - m))`, in float32.

Rotary (half-split form) turns the FIRST `rotary_dim` (64) dims of each
query and key head and leaves the rest; `v` is multiplied by
`value_scale` before attention; scores are `q k^T / sqrt(head_dim)`.  The
second half is a dense SwiGLU where `moe_layers[i]` is 0 and otherwise
`sigmoid_topk_route` over ALL `n_routed_experts` with the top-k taken
over all of them, of which this chip HOLDS `experts_held` from
`expert_offset` on (`parallel/moe.dropless_moe(held=)`); no shared
expert.  Untied embedding and head, float32 logits.

THE CACHE is of both kinds, both for attention
(`serve/engine_model.WindowFullEngineModel`).  The full layers' rows are
PAGED: `k` `[full layers, NB, BS, KV * 192]` and `v` `[.., KV * 128]`, a
token's heads folded side by side into one row of whole lanes.  A window
layer never reads a row again once the sequence is `window` tokens past
it, so its rows lie in a per-slot RING: `swa_k` `[window layers, slots,
ring, KV_w * 192]`, `swa_v` `[.., KV_w * 128]`, the row of position `p`
at `p mod ring`, and `ring` is the window (`ring_rows`): a step writes
its row over the one that just left the window and reads all `ring`
rows, each masked by the position it holds (`p - ((p - r) mod ring)`,
valid from 0 on).  Nothing of a window layer is paged, and a slot's
bytes do not grow with its context.

Three programs: `decode_step` (a row a sequence; the full layers through
the block table, by the paged Pallas kernels or in plain XLA), `forward`
(a packed row of whole prompts from position 0: segment AND causal AND
window masks, each prompt's last `ring` window rows left in its slot)
and `forward_chunk` (the next `N` tokens of ONE sequence behind its own
cached rows: a chunk of a long prompt.  A full layer writes the chunk's
rows into the sequence's blocks and attends THROUGH THE TABLE key block
by key block with a running softmax, the loop's trip count the blocks
the chunk can see, never a `[chunk, context]` score array; a window
layer attends the slot's ring and the chunk and leaves the ring as it
stands at the chunk's end).  All window attention is banded: a query
block of `ring` rows meets the `2 x ring` keys it can see.

WHERE A FULL LAYER'S PREFILL FOLDS.  `paged_kernel` (the engine's
`paged` route: the chip, and the interpreter in the CPU tests): in ONE
fused kernel a layer, `ops/prefill_attention.py`, the scores in VMEM;
`forward` hands it the row's own keys, `forward_chunk` the table's rows
gathered once into contiguous K and V (`_fused_full`), and it skips the
key blocks the masks would zero whole.  Anywhere else: `_attend_blocks`,
the same fold as a `fori_loop` in plain XLA, a key block's scores `[KV,
G, N, 512]` float32 through HBM.  The window layers' `_banded` and every
decode attention are the same on both routes but for the paged decode
kernels.

Layers are a LIST of per-layer dicts and the programs unroll them: the
two kinds differ in their leaves' shapes.  `jax.named_scope`s
`full_attn`, `swa_attn`, `swa_ring_write`, `dense_mlp`, `moe_router`,
`moe_routed`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.deepseek_v3 import _swiglu
from ray_tpu.models.llama import Packed, _apply, _embed, _lm_head, _rms_norm
from ray_tpu.ops import paged_attention as _pa
from ray_tpu.ops.prefill_attention import prefill_attention
from ray_tpu.parallel.moe import dropless_moe

F32 = jnp.float32
FULL, SWA = 0, 1
# the published order: a full layer, then (window x 4, full), then
# (window x 5, full) x 7: nine full layers of 48
LAYER_PATTERN = (FULL,) + (SWA,) * 4 + ((FULL,) + (SWA,) * 5) * 7 + (FULL,)
MOE_LAYERS = (0,) + (1,) * 47
ROUTE_EPS = 1e-20
NEG = -1e30
# keys a full layer's prefill folds into its running softmax at a time
# in plain XLA ...
KEY_BLOCK = 512
# ... and in the fused kernel, whose scores stay in VMEM: (query rows,
# each all 16 heads of a group; keys).  Measured on the v5e at 2,048
# rows behind 0 / 2,048 / 4,096 / 6,144: 128 x 512 2.09 / 4.30 / 6.51 /
# 8.71 ms, 128 x 1024 1.76 / 3.04 / 4.28 / 5.60, 256 x 1024 the same
# within 2%, 128 x 2048 2.19 / 3.55 / 4.92 / 6.30 (the XLA fold: 4.81 /
# 9.29 / 13.8 / 18.3; PERF.md section 6, PR 53)
FUSED_BLOCKS = (128, 1024)


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    max_seq_len: int = 1048576
    dim: int = 4096
    layer_pattern: Tuple[int, ...] = LAYER_PATTERN   # 0 full, 1 window
    moe_layers: Tuple[int, ...] = MOE_LAYERS         # 0 dense, 1 experts
    head_dim: int = 192               # queries and keys
    v_head_dim: int = 128
    rotary_dim: int = 64              # int(head_dim * partial_rotary_factor)
    value_scale: float = 0.707
    # full layers
    n_heads: int = 64
    n_kv_heads: int = 4
    rope_theta: float = 1e7
    full_sink: bool = False
    # window layers
    swa_n_heads: int = 64
    swa_n_kv_heads: int = 8
    swa_rope_theta: float = 1e4
    window: int = 128                 # tokens attended, the token itself one
    swa_sink: bool = True
    # second halves
    intermediate: int = 16384
    moe_intermediate: int = 2048
    n_routed_experts: int = 256       # the router's width
    experts_held: int = 256           # this chip's share of them ...
    expert_offset: int = 0            # ... from this expert on
    top_k: int = 8
    routed_scale: float = 1.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "dense"          # what the engine's packed prefill asks

    @property
    def n_layers(self) -> int:
        return len(self.layer_pattern)

    @property
    def n_full_layers(self) -> int:
        return sum(k == FULL for k in self.layer_pattern)

    @property
    def n_swa_layers(self) -> int:
        return self.n_layers - self.n_full_layers

    @property
    def n_moe_layers(self) -> int:
        return sum(self.moe_layers)

    @property
    def ring_rows(self) -> int:
        """Rows of a slot's ring: the window.  A step writes position
        `p` over `p - window`, the one row that just left the window,
        BEFORE it reads, so `window` rows hold exactly what it may see;
        a prefill reads the ring beside its own rows and needs the
        `window - 1` before its first.  (The published 128 is whole
        lane tiles and whole cache blocks; a longer ring would only be
        rows that every read masks.)"""
        return self.window

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MimoV2Config":
        return MimoV2Config(
            vocab_size=vocab_size, max_seq_len=256, dim=64,
            layer_pattern=(FULL, SWA, SWA, FULL, SWA),
            moe_layers=(0, 1, 1, 1, 1), head_dim=24, v_head_dim=16,
            rotary_dim=8, n_heads=4, n_kv_heads=2, swa_n_heads=4,
            swa_n_kv_heads=4, window=8, intermediate=128,
            moe_intermediate=32, n_routed_experts=16, experts_held=4,
            expert_offset=4, top_k=4, dtype=jnp.float32)


class _Attn(NamedTuple):
    """One layer kind's attention widths."""
    heads: int
    kv: int
    theta: float
    sink: bool


def attn_form(cfg: MimoV2Config, kind: int) -> _Attn:
    if kind == FULL:
        return _Attn(cfg.n_heads, cfg.n_kv_heads, cfg.rope_theta,
                     cfg.full_sink)
    return _Attn(cfg.swa_n_heads, cfg.swa_n_kv_heads, cfg.swa_rope_theta,
                 cfg.swa_sink)


def layer_shapes(cfg: MimoV2Config, i: int) -> Dict[str, tuple]:
    """Layer `i`'s leaves: its attention's, then its second half's."""
    a, D = attn_form(cfg, cfg.layer_pattern[i]), cfg.dim
    dk, dv = cfg.head_dim, cfg.v_head_dim
    out = {"attn_norm": (D,),
           "wqkv": (D, a.heads * dk + a.kv * (dk + dv)),
           "wo": (a.heads * dv, D), "mlp_norm": (D,)}
    if a.sink:
        out["sink"] = (a.heads,)
    if cfg.moe_layers[i]:
        Eh, Im = cfg.experts_held, cfg.moe_intermediate
        out.update({"router": (D, cfg.n_routed_experts),
                    "router_bias": (cfg.n_routed_experts,),
                    "e_gate": (Eh, D, Im), "e_up": (Eh, D, Im),
                    "e_down": (Eh, Im, D)})
    else:
        I = cfg.intermediate
        out.update({"w_gate": (D, I), "w_up": (D, I), "w_down": (I, D)})
    return out


F32_LEAVES = ("router", "router_bias", "sink")


def init_params(cfg: MimoV2Config, key: jax.Array, std: float = 0.02):
    """Random weights in the tree the functions below read: `tok_emb`,
    `final_norm`, `lm_head`, and `layers`, one dict a layer.  The sinks
    are N(0, 1): a zero sink would be one more key of score 0."""
    layers = []
    for i in range(cfg.n_layers):
        lk, leaves = jax.random.fold_in(key, i), {}
        for j, (name, shape) in enumerate(sorted(layer_shapes(cfg, i).items())):
            dt = F32 if name in F32_LEAVES else cfg.dtype
            if name.endswith("norm"):
                leaves[name] = jnp.ones(shape, dt)
            else:
                s = 1.0 if name == "sink" else std
                leaves[name] = (jax.random.normal(
                    jax.random.fold_in(lk, j), shape, F32) * s).astype(dt)
        layers.append(leaves)
    k = jax.random.split(jax.random.fold_in(key, 10_000), 2)
    return {
        "tok_emb": (jax.random.normal(k[0], (cfg.vocab_size, cfg.dim))
                    * std).astype(cfg.dtype),
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": (jax.random.normal(k[1], (cfg.dim, cfg.vocab_size))
                    * std).astype(cfg.dtype),
        "layers": layers,
    }


def leaf_index(cfg: MimoV2Config, i: int) -> int:
    """Layer `i`'s place among the layers of its own kind: its row of
    the cache leaves that kind holds."""
    return sum(k == cfg.layer_pattern[i] for k in cfg.layer_pattern[:i])


# ----------------------------------------------------------------------
# parts
# ----------------------------------------------------------------------
def _rope_partial(x, theta: float, pos, rot: int):
    """Half-split rotary on the first `rot` dims of the last axis, the
    rest untouched: x [N, H, d], pos [N]."""
    half = rot // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], axis=-1)


def _qkv(cfg, a: _Attn, layer, h, pos):
    """h [N, D] normed, pos [N] -> (q [N, KV, G, dk] rotated, k [N, KV,
    dk] rotated, v [N, KV, dv] scaled): a query head beside the others
    of its key/value head."""
    dt, dk, dv = cfg.dtype, cfg.head_dim, cfg.v_head_dim
    N = h.shape[0]
    qkv = _apply(h, layer["wqkv"], dt)
    nq, nk = a.heads * dk, a.kv * dk
    q = _rope_partial(qkv[:, :nq].reshape(N, a.heads, dk), a.theta, pos,
                      cfg.rotary_dim)
    k = _rope_partial(qkv[:, nq:nq + nk].reshape(N, a.kv, dk), a.theta, pos,
                      cfg.rotary_dim)
    v = (qkv[:, nq + nk:].astype(F32) * cfg.value_scale).astype(dt)
    return (q.reshape(N, a.kv, a.heads // a.kv, dk), k,
            v.reshape(N, a.kv, dv))


def _sink_of(a: _Attn, layer):
    """The layer's sinks as `[KV, G]` float32, or None."""
    if not a.sink:
        return None
    return layer["sink"].astype(F32).reshape(a.kv, a.heads // a.kv)


def _attend(cfg, q, k, v, mask, sink):
    """One softmax over all of `k`: q [..., Tq, KV, G, dk], k [..., Tk,
    KV, dk], v [..., Tk, KV, dv], mask [..., Tq, Tk] bool, sink [KV, G]
    float32 or None -> [..., Tq, KV, G, dv] float32.  The sink joins the
    max and the sum and weighs no value."""
    dt = cfg.dtype
    s = jnp.einsum("...qkgd,...tkd->...kgqt", q, k,
                   preferred_element_type=F32) * cfg.head_dim ** -0.5
    s = jnp.where(mask[..., None, None, :, :], s, NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink[:, :, None, None])
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        l = l + jnp.exp(sink[:, :, None, None] - m)
    o = jnp.einsum("...kgqt,...tkd->...qkgd", p.astype(dt), v,
                   preferred_element_type=F32)
    return o / jnp.moveaxis(l, -2, -4)


def _attend_blocks(cfg, q, keys_of, n_blocks, sink):
    """A running softmax over key blocks: q [Tq, KV, G, dk];
    `keys_of(j) -> (k [KB, KV, dk], v [KB, KV, dv], mask [Tq, KB])`;
    `n_blocks` (traced) how many of them any row can see -> [Tq, KV, G,
    dv] float32.  What is alive at once is one block's scores."""
    dt = cfg.dtype
    Tq, KV, G, _ = q.shape
    scale = cfg.head_dim ** -0.5

    def fold(j, carry):
        m, l, acc = carry
        k, v, mask = keys_of(j)
        s = jnp.einsum("qkgd,tkd->kgqt", q, k,
                       preferred_element_type=F32) * scale
        s = jnp.where(mask[None, None], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        # a row that has seen no key yet has m_new = NEG and p = 1 for
        # every masked column: weigh them out
        p = jnp.where(mask[None, None], jnp.exp(s - m_new), 0.0)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "kgqt,tkd->kgqd", p.astype(dt), v, preferred_element_type=F32)
        return m_new, l, acc

    m0 = jnp.full((KV, G, Tq, 1), NEG, F32)
    if sink is not None:
        m0 = jnp.maximum(m0, sink[:, :, None, None])
    l0 = (jnp.zeros((KV, G, Tq, 1), F32) if sink is None
          else jnp.exp(sink[:, :, None, None] - m0))
    _, l, acc = lax.fori_loop(
        0, n_blocks, fold,
        (m0, l0, jnp.zeros((KV, G, Tq, cfg.v_head_dim), F32)))
    o = acc / jnp.where(l == 0.0, 1.0, l)
    return jnp.moveaxis(o, 2, 0)


def _fused_full(cfg, q, k, v, qseg, kseg, lo, sink, interpret):
    """`_attend_blocks`' fold as ONE kernel whose scores stay in VMEM
    (`ops/prefill_attention.py`): q [N, KV, G, dk] whose row `i` is key
    row `lo + i` of the contiguous k [S, KV, dk] / v [S, KV, dv], seen
    where the segments agree, the key is not past the query and the
    query is real -> [N, KV, G, dv] in the model's dtype."""
    if sink is not None:
        raise ValueError("the fused prefill attention has no sink column")
    return prefill_attention(
        q, k, v, qseg, kseg, lo, scale=cfg.head_dim ** -0.5,
        block_q=FUSED_BLOCKS[0], block_k=FUSED_BLOCKS[1], interpret=interpret)


def _banded(cfg, q, k, v, qpos, qseg, prev, sink):
    """Window attention of a row of `N` tokens whose sequences lie end
    to end: query block `i` of `ring` rows meets key rows `(i - 1) *
    ring .. (i + 1) * ring`, all a window can reach.  q [N, KV, G, dk],
    k [N, KV, dk], v [N, KV, dv]; `qpos` [N] a token's position in its
    own sequence, `qseg` [N] its sequence (-1: padding); `prev` = (k, v,
    pos) the `ring` rows before the row's first token (one sequence's,
    in position order, which continues here as segment 0) or None ->
    [N, KV, G, dv] float32."""
    N, R = q.shape[0], cfg.ring_rows
    pad = -N % R
    if pad:
        q, k, v = (jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
                   for x in (q, k, v))
        qpos = jnp.pad(qpos, (0, pad))
        qseg = jnp.pad(qseg, (0, pad), constant_values=-1)
    if prev is None:
        pk, pv = (jnp.zeros((R,) + x.shape[1:], x.dtype) for x in (k, v))
        ppos, pseg = jnp.zeros((R,), jnp.int32), jnp.full((R,), -2, jnp.int32)
    else:
        pk, pv, ppos = prev
        pseg = jnp.where(ppos >= 0, 0, -2)
    nb = (N + pad) // R

    def pairs(x, px):
        """[nb, 2R, ...]: each block's rows behind the block before."""
        x = jnp.concatenate([px.astype(x.dtype), x]).reshape(
            (nb + 1, R) + x.shape[1:])
        return jnp.concatenate([x[:-1], x[1:]], axis=1)

    kk, vv = pairs(k, pk), pairs(v, pv)
    kpos, kseg = pairs(qpos, ppos), pairs(qseg, pseg)
    qp, qs = qpos.reshape(nb, R), qseg.reshape(nb, R)
    d = qp[:, :, None] - kpos[:, None, :]
    mask = ((qs[:, :, None] == kseg[:, None, :]) & (d >= 0)
            & (d < cfg.window) & (qs[:, :, None] >= 0))
    o = _attend(cfg, q.reshape((nb, R) + q.shape[1:]), kk, vv, mask, sink)
    return o.reshape((nb * R,) + o.shape[2:])[:N]


def _ring_index(cfg, T):
    """Which position each row of a ring holds once its sequence is `T`
    tokens long: `[.., ring]` positions, negative where none was ever
    written (`T` [..] int32)."""
    R = cfg.ring_rows
    r = jnp.arange(R, dtype=jnp.int32)
    last = T[..., None] - 1
    return last - (last - r) % R


def _ffn(cfg, layer, x, *, kernel, interpret, row_mask):
    """The second half of a layer: x [N, D] -> (x + y, stats or None)."""
    h = _rms_norm(x, layer["mlp_norm"].astype(cfg.dtype), cfg.norm_eps)
    if "router" not in layer:
        with jax.named_scope("dense_mlp"):
            return x + _swiglu(h, layer["w_gate"], layer["w_up"],
                               layer["w_down"], cfg.dtype), None
    y, stats = dropless_moe(
        h, layer, top_k=cfg.top_k, scale=cfg.routed_scale,
        route_eps=ROUTE_EPS, dtype=cfg.dtype, kernel=kernel,
        interpret=interpret, row_mask=row_mask,
        held=(cfg.expert_offset, cfg.experts_held))
    return x + y, stats


def _out(cfg, layer, o):
    """o [N, KV, G, dv] float32 -> the half's output [N, D]."""
    return _apply(o.astype(cfg.dtype).reshape(o.shape[0], -1), layer["wo"],
                  cfg.dtype)


def _head(cfg, params, x):
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    return _lm_head(x, params, cfg.dtype)


# ----------------------------------------------------------------------
# prefill: a packed row of whole prompts
# ----------------------------------------------------------------------
def forward(cfg: MimoV2Config, params: Dict, tokens: jax.Array, ring=None,
            *, packed: Optional[Packed] = None, slots=None,
            kernel: bool = False, interpret: bool = False,
            paged_kernel: bool = False):
    """tokens [1, T] -> (logits float32, (ks, vs), ring).

    `packed` None: one prompt from position 0, right-padded: logits `[1,
    T, vocab]`.  `packed` (`llama.Packed`): up to `K` prompts end to end,
    a token attends inside its own prompt only (and, in a window layer,
    inside its window), logits `[1, K, vocab]` of the rows `packed.last`
    names.  `ks` `[full layers, 1, T, KV * dk]`, `vs` `[.., KV * dv]`:
    the rows the full layers cache, as the pools hold them.  `ring` =
    `(swa_k, swa_v)` `[window layers, slots, ring, ..]` with `slots` [K]
    (past the last: dropped): each prompt's last `ring` window rows go
    into its slot's ring at `position mod ring`; None: none is kept.
    `paged_kernel`: a full layer's attention is ONE fused kernel
    (`_fused_full`); else the same fold in plain XLA."""
    B, T = tokens.shape
    if B != 1:
        raise ValueError("a prefill takes one row")
    dt = cfg.dtype
    if packed is not None and packed.seg is not None:
        pos, seg = packed.pos, packed.seg
    else:
        pos = jnp.arange(T, dtype=jnp.int32)
        seg = jnp.zeros((T,), jnp.int32)
    real = seg >= 0
    # whole key blocks: the widest that divides the row
    KB = max(d for d in range(1, min(KEY_BLOCK, T) + 1) if T % d == 0)
    row = jnp.arange(T, dtype=jnp.int32)
    if ring is not None:
        # each prompt's ring at its end: which row of the packed row
        # holds the position each ring row keeps
        lens = pos[packed.last] + 1                               # [K]
        held = _ring_index(cfg, lens)                             # [K, R]
        src = jnp.clip(packed.last[:, None] - (lens[:, None] - 1 - held),
                       0, T - 1)
    x = _embed(params, tokens[0], dt).astype(dt)
    ks, vs = [], []
    for i, layer in enumerate(params["layers"]):
        kind = cfg.layer_pattern[i]
        a, li = attn_form(cfg, kind), leaf_index(cfg, i)
        h = _rms_norm(x, layer["attn_norm"].astype(dt), cfg.norm_eps)
        sink = _sink_of(a, layer)
        if kind == FULL:
            with jax.named_scope("full_attn"):
                q, k, v = _qkv(cfg, a, layer, h, pos)
                if paged_kernel:
                    o = _fused_full(cfg, q, k, v, seg, seg, 0, sink,
                                    interpret)
                else:
                    def keys_of(j, k=k, v=v):
                        cut = lambda t: lax.dynamic_slice_in_dim(  # noqa: E731
                            t, j * KB, KB, 0)
                        mask = ((seg[:, None] == cut(seg)[None, :])
                                & (row[:, None] >= cut(row)[None, :])
                                & real[:, None])
                        return cut(k), cut(v), mask

                    o = _attend_blocks(cfg, q, keys_of, T // KB, sink)
                ks.append(k.reshape(T, -1))
                vs.append(v.reshape(T, -1))
        else:
            with jax.named_scope("swa_attn"):
                q, k, v = _qkv(cfg, a, layer, h, pos)
                o = _banded(cfg, q, k, v, pos, seg, None, sink)
            if ring is not None:
                with jax.named_scope("swa_ring_write"):
                    ring = tuple(
                        r.at[li, slots].set(
                            t.reshape(T, -1)[src].astype(r.dtype),
                            mode="drop")
                        for r, t in zip(ring, (k, v)))
        x = x + _out(cfg, layer, o)
        x, _ = _ffn(cfg, layer, x, kernel=kernel, interpret=interpret,
                    row_mask=real)
    if packed is not None:
        x = x[packed.last]
    kv = (jnp.stack(ks)[:, None], jnp.stack(vs)[:, None])
    return _head(cfg, params, x)[None], kv, ring


# ----------------------------------------------------------------------
# prefill: a chunk of ONE long prompt, behind its cached rows
# ----------------------------------------------------------------------
def forward_chunk(cfg: MimoV2Config, params: Dict, tokens: jax.Array,
                  lo, n, cache, table: jax.Array, slot, *,
                  kernel: bool = False, interpret: bool = False,
                  paged_kernel: bool = False):
    """Tokens `lo .. lo + n` of one sequence, `tokens` [N] (the first
    `n` real), behind the `lo` tokens that earlier chunks cached:
    `cache` = `(k, v, swa_k, swa_v)`, `table` [W] the sequence's blocks
    (naming every block up to the chunk's last), `slot` its slot; `lo`
    starts a cache block.  A full layer writes the chunk's rows into the
    sequence's blocks, whole blocks (what padding computed lies past the
    sequence's `pos`, masked until decoding overwrites it; a block of
    padding alone is written nowhere), then attends positions `0 .. lo +
    n` through the table in key blocks of `KEY_BLOCK` under a running
    softmax (`paged_kernel`: the table's rows gathered ONCE a layer
    into contiguous K and V and folded by one fused kernel,
    `_fused_full`); a window layer attends the slot's ring (the `ring`
    rows before `lo`) beside the chunk and leaves the ring as it stands
    at `lo + n`.  Returns (logits [vocab] float32 of the chunk's last real
    token, the cache)."""
    dt = cfg.dtype
    k_pool, v_pool, ring_k, ring_v = cache
    NB, BS = k_pool.shape[1:3]
    N, W, R = tokens.shape[0], table.shape[0], cfg.ring_rows
    if N % BS:
        raise ValueError(f"{N} rows are no whole cache blocks of {BS}")
    row = jnp.arange(N, dtype=jnp.int32)
    pos = lo + row
    real = row < n
    seg = jnp.where(real, 0, -1)
    # the cache block each block of BS rows is written to; padding: none
    first = lo // BS + jnp.arange(N // BS)
    wblk = jnp.where(row[::BS] < n, table[jnp.clip(first, 0, W - 1)], NB)
    # key blocks of whole cache blocks, through the table
    PB = max(1, min(KEY_BLOCK // BS, W))
    KB = PB * BS
    n_blocks = (lo + n + KB - 1) // KB
    # the ring as it stood at `lo`, in position order `lo - R .. lo - 1`
    ppos = lo - R + jnp.arange(R, dtype=jnp.int32)
    prow = ppos % R
    # ... and the positions it holds at `lo + n`, by their row among
    # the ring's old rows and the chunk's
    held = _ring_index(cfg, lo + n)                               # [R]
    src = jnp.clip(held - (lo - R), 0, R + N - 1)
    x = _embed(params, tokens, dt).astype(dt)
    for i, layer in enumerate(params["layers"]):
        kind = cfg.layer_pattern[i]
        a, li = attn_form(cfg, kind), leaf_index(cfg, i)
        h = _rms_norm(x, layer["attn_norm"].astype(dt), cfg.norm_eps)
        sink = _sink_of(a, layer)
        q, k, v = _qkv(cfg, a, layer, h, pos)
        if kind == FULL:
            with jax.named_scope("full_attn"):
                k_pool, v_pool = (
                    pool.at[li, wblk].set(
                        t.reshape(N // BS, BS, -1).astype(pool.dtype),
                        mode="drop")
                    for pool, t in ((k_pool, k), (v_pool, v)))

                if paged_kernel:
                    o = _fused_full(
                        cfg, q,
                        k_pool[li, table].reshape(W * BS, a.kv, -1).astype(dt),
                        v_pool[li, table].reshape(W * BS, a.kv, -1).astype(dt),
                        seg, jnp.zeros((W * BS,), jnp.int32), lo, sink,
                        interpret)
                else:
                    def keys_of(j, li=li, a=a, k_pool=k_pool, v_pool=v_pool):
                        blk = table[jnp.clip(j * PB + jnp.arange(PB), 0,
                                             W - 1)]
                        kpos = j * KB + jnp.arange(KB)
                        mask = (kpos[None, :] <= pos[:, None]) & real[:, None]
                        return (
                            k_pool[li, blk].reshape(KB, a.kv, -1).astype(dt),
                            v_pool[li, blk].reshape(KB, a.kv, -1).astype(dt),
                            mask)

                    o = _attend_blocks(cfg, q, keys_of, n_blocks, sink)
        else:
            with jax.named_scope("swa_attn"):
                pk = ring_k[li, slot][prow].reshape(R, a.kv, -1).astype(dt)
                pv = ring_v[li, slot][prow].reshape(R, a.kv, -1).astype(dt)
                o = _banded(cfg, q, k, v, pos, seg, (pk, pv, ppos), sink)
            with jax.named_scope("swa_ring_write"):
                ring_k, ring_v = (
                    r.at[li, slot].set(jnp.concatenate(
                        [p.reshape(R, -1), t.reshape(N, -1)])[src]
                        .astype(r.dtype))
                    for r, p, t in ((ring_k, pk, k), (ring_v, pv, v)))
        x = x + _out(cfg, layer, o)
        x, _ = _ffn(cfg, layer, x, kernel=kernel, interpret=interpret,
                    row_mask=real)
    return (_head(cfg, params, x[jnp.maximum(n - 1, 0)][None])[0],
            (k_pool, v_pool, ring_k, ring_v))


# ----------------------------------------------------------------------
# decode: one step through both caches
# ----------------------------------------------------------------------
def decode_step(cfg: MimoV2Config, params: Dict, token: jax.Array, cache,
                pos, tables, *, live=None, kernel: bool = False,
                interpret: bool = False, paged_kernel: bool = False):
    """One decode step at per-row positions: token [B], pos [B], `cache`
    = `(k, v, swa_k, swa_v)`, `tables` [B, W] each row's blocks (row b
    sits in slot b).  A full layer appends its row to the paged pools
    and attends positions `0 .. pos` through the table
    (`paged_kernel`: the Pallas kernels of `ops/paged_attention.py` on
    the folded pools, keys 192 and values 128 wide; else plain XLA, the
    table's blocks gathered side by side); a window layer writes its row
    into the slot's ring at `pos mod ring` and reads the ring, each row
    masked by the position it holds.  Returns (logits [B, vocab]
    float32, cache, stats) with `stats` = `experts_touched`, `load_max`
    over the HELD experts.

    `live` [B] bool (the engine's `pos < stop`; None: every row): a row
    that is not live writes nothing, neither block nor ring, and is
    routed to no expert; what it attends is nobody's."""
    dt = cfg.dtype
    k_pool, v_pool, ring_k, ring_v = cache
    NB, BS = k_pool.shape[1:3]
    B, W = tables.shape
    R = cfg.ring_rows
    if paged_kernel:
        w_pos, a_pos = _pa.dead_row_positions(pos, live, tables, BS)
    else:
        blk = jnp.take_along_axis(
            tables, jnp.clip(pos // BS, 0, W - 1)[:, None], axis=1)[:, 0]
        if live is not None:
            blk = jnp.where(live, blk, NB)
        valid = jnp.arange(W * BS)[None, :] <= pos[:, None]
    rows = jnp.arange(B)
    ring_row = rows if live is None else jnp.where(live, rows, B)
    ring_ok = (_ring_index(cfg, pos + 1) >= 0)[:, None, :]        # [B, 1, R]
    x = _embed(params, token, dt).astype(dt)                       # [B, D]
    touched = jnp.zeros((), jnp.int32)
    load_max = jnp.zeros((), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        kind = cfg.layer_pattern[i]
        a, li = attn_form(cfg, kind), leaf_index(cfg, i)
        h = _rms_norm(x, layer["attn_norm"].astype(dt), cfg.norm_eps)
        sink = _sink_of(a, layer)
        q, k, v = _qkv(cfg, a, layer, h, pos)
        k, v = k.reshape(B, -1), v.reshape(B, -1)
        if kind == FULL:
            with jax.named_scope("full_attn"):
                if paged_kernel:
                    k_pool, v_pool = _pa.paged_kv_append(
                        k_pool, v_pool, k.astype(k_pool.dtype),
                        v.astype(v_pool.dtype), tables, w_pos, li,
                        interpret=interpret)
                    o = _pa.paged_decode_attention(
                        q.reshape(B, a.heads, -1), k_pool, v_pool, tables,
                        a_pos, li, interpret=interpret)
                    o = o.reshape(B, a.kv, a.heads // a.kv, -1)
                else:
                    k_pool, v_pool = (
                        pool.at[li, blk, pos % BS].set(
                            t.astype(pool.dtype), mode="drop")
                        for pool, t in ((k_pool, k), (v_pool, v)))
                    o = _attend(
                        cfg, q[:, None],
                        k_pool[li, tables].reshape(B, W * BS, a.kv, -1)
                        .astype(dt),
                        v_pool[li, tables].reshape(B, W * BS, a.kv, -1)
                        .astype(dt), valid[:, None, :], sink)[:, 0]
        else:
            with jax.named_scope("swa_ring_write"):
                ring_k, ring_v = (
                    r.at[li, ring_row, pos % R].set(t.astype(r.dtype),
                                                    mode="drop")
                    for r, t in ((ring_k, k), (ring_v, v)))
            with jax.named_scope("swa_attn"):
                o = _attend(
                    cfg, q[:, None],
                    ring_k[li].reshape(B, R, a.kv, -1).astype(dt),
                    ring_v[li].reshape(B, R, a.kv, -1).astype(dt),
                    ring_ok, sink)[:, 0]
        x = x + _out(cfg, layer, o)
        x, stats = _ffn(cfg, layer, x, kernel=kernel, interpret=interpret,
                        row_mask=live)
        if stats is not None:
            touched = touched + stats["experts_touched"]
            load_max = jnp.maximum(load_max, stats["load_max"])
    return (_head(cfg, params, x), (k_pool, v_pool, ring_k, ring_v),
            {"experts_touched": touched, "load_max": load_max})
