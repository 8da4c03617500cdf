"""dots3-note-lineage decoder for SERVING: latent attention (MLA) of TWO
forms in one model, and a share of a wide expert layer.

The language model of `model_type: dots3_note` checkpoints
(`dots-studio/dots3-note-prev`, 288B-A17B; the published `config.json`).
Pre-norm RMSNorm, a residual around each half.  A layer's first half is
one of

- a FULL layer (`layer_types[i] == "full_attention"`): DeepSeek-V3's
  latent attention with a query rank (`c_q = s_q RMSNorm(x W_qa)`, `q =
  c_q W_qb`; `c_kv, k_r = split(x W_kva)`, `c_kv = s_kv RMSNorm(c_kv)`;
  the two scalars are `sqrt(dim / rank)`, the rotary part unscaled) and
  a LEARNED SPARSE SELECTION over it (DeepSeek-V3.2-Exp's indexer):
  `qI = c_q W_qI` `[index_n_heads, index_head_dim]`, `kI = LayerNorm(x
  W_kI)`, rotary on the first `qk_rope_dim` of both, `w = x W_w
  index_n_heads^-0.5 index_head_dim^-0.5`, `I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s])`, and token `t` attends the `index_topk`
  largest `I[t, s]` over `s <= t` (all of them while there are fewer);
  the softmax runs over the selected rows only;
- a WINDOW layer (`"sliding_attention"`): the same latent attention at
  its own widths (`swa_*`: heads, ranks, nope width, rotary base), no
  indexer, `t` attends `s` with `0 <= t - s < window`.

Both end in a head-wise gate, `g = sigmoid(x W_g)` one a head, on the
head's output before `W_o`.  The second half is a dense SwiGLU in the
first `first_k_dense` layers and after them `sigmoid_topk_route` over
ALL `n_routed_experts` with the top-k taken over all of them, of which
this chip HOLDS `experts_held` from `expert_offset` on
(`parallel/moe.dropless_moe(held=)`: a pair whose expert lives on
another chip adds nothing here), plus the always-on shared expert.

ATTENTION IS ALWAYS THE ABSORBED FORM here, prefill too: a query goes
into the latent space (`q_nope W_uk`), scores and the weighted sum run
on cached rows as they lie, the result leaves through `W_uv`.  A
selection makes each query's key set its own, and gathering 2,048
latent rows a query (576 values) is a quarter of gathering their
expanded per-head keys and values.

THE CACHE is three paged leaves on ONE block table a sequence
(`serve/engine_model.SparseLatentEngineModel`): `latent` `[full
layers, NB, BS, 576 -> 640]`, `index_k` `[full layers, NB, BS, 128]`,
`swa_latent` `[window layers, NB, BS, 1088 -> 1152]`.  BOTH programs
reach it through the table in plain XLA, on any backend, and have one
form: a layer writes its new rows in (a decode step one row a
sequence, a prefill whole blocks), a full layer scores a row's whole
context with the indexer, takes the top-k and gathers the selected
rows, and a window layer gathers only the blocks the window can touch
(a START position: time O(window) whatever the context; the blocks
before it are not freed).  A decode step walks the slots in groups of
`ROW_GROUP` rows so that a group's index scores `[rows, index heads,
context]` stay small, and gathers a row's selection from the pool by
flat index; a prefill (`forward_with_prefix`) holds the next tokens of
SEVERAL sequences, each behind its own cached rows, walks them in query
blocks of `QUERY_BLOCK` rows, copies a block's sequence's own blocks
side by side once and gathers its 64 queries' selections from that
copy, and walks only the blocks that hold a token: the weights are
read once for all of them.

Layers are a LIST of per-layer dicts and the programs unroll them: the
two kinds differ in every leaf's shape, and a cut of the published
depth to a handful of layers is what one chip holds.  `jax.named_scope`s
`dsa_index`, `dsa_select`, `dsa_attn`, `swa_attn`, `attn_gate`,
`dense_mlp`, `moe_router`, `moe_routed`, `moe_shared`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.deepseek_v3 import _rope_interleaved, _swiglu
from ray_tpu.models.llama import _apply, _embed, _lm_head, _rms_norm
from ray_tpu.parallel.moe import dropless_moe

F32 = jnp.float32
FULL, SWA = "full_attention", "sliding_attention"
# the published order: two full layers, then (window x 3, full) periods
LAYER_TYPES = (FULL, FULL) + (SWA, SWA, SWA, FULL) * 11
ROUTE_EPS = 1e-20
# rows a layer scores, selects and gathers for at a time: of the slots
# (decode), of one sequence's queries (prefill: every sequence of a
# packed prefill starts on a multiple of it)
ROW_GROUP = 8
QUERY_BLOCK = 64
NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 152064
    max_seq_len: int = 524288
    dim: int = 5120
    layer_types: Tuple[str, ...] = LAYER_TYPES
    # full layers
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # window layers
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_dim: int = 192
    swa_qk_rope_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    window: int = 513                 # tokens attended, the token itself one
    qkv_rescale: bool = True          # `apply_mla_qkv_lora_rescale`
    # second halves
    intermediate: int = 13824
    moe_intermediate: int = 1536
    n_routed_experts: int = 256       # the router's width
    experts_held: int = 256           # this chip's share of them ...
    expert_offset: int = 0            # ... from this expert on
    n_shared_experts: int = 1
    top_k: int = 8
    first_k_dense: int = 1
    routed_scale: float = 1.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "dense"

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_full_layers(self) -> int:
        return sum(t == FULL for t in self.layer_types)

    @property
    def n_swa_layers(self) -> int:
        return self.n_layers - self.n_full_layers

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def swa_latent_dim(self) -> int:
        return self.swa_kv_lora_rank + self.swa_qk_rope_dim

    def window_blocks(self, bs: int) -> int:
        """Blocks of `bs` tokens a window can touch."""
        return -(-(self.window - 1) // bs) + 1

    @staticmethod
    def tiny(vocab_size: int = 256) -> "Dots3Config":
        return Dots3Config(
            vocab_size=vocab_size, max_seq_len=256, dim=64,
            layer_types=(FULL, FULL, SWA, SWA, SWA), n_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
            v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=12,
            swa_n_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=24,
            swa_qk_nope_dim=24, swa_qk_rope_dim=8, swa_v_head_dim=16,
            window=10, intermediate=128, moe_intermediate=32,
            n_routed_experts=16, experts_held=4, expert_offset=4, top_k=4,
            dtype=jnp.float32)


class _Attn(NamedTuple):
    """One attention form's widths."""
    heads: int
    q_rank: int
    rank: int
    nope: int
    rope: int
    v: int
    theta: float
    s_q: float
    s_kv: float

    @property
    def latent(self) -> int:
        return self.rank + self.rope

    @property
    def scale(self) -> float:
        return (self.nope + self.rope) ** -0.5


def attn_form(cfg: Dots3Config, kind: str) -> _Attn:
    if kind == FULL:
        a = (cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
             cfg.qk_rope_dim, cfg.v_head_dim, cfg.rope_theta)
    elif kind == SWA:
        a = (cfg.swa_n_heads, cfg.swa_q_lora_rank, cfg.swa_kv_lora_rank,
             cfg.swa_qk_nope_dim, cfg.swa_qk_rope_dim, cfg.swa_v_head_dim,
             cfg.swa_rope_theta)
    else:
        raise ValueError(f"layer type {kind!r}")
    on = cfg.qkv_rescale
    return _Attn(*a, s_q=math.sqrt(cfg.dim / a[1]) if on else 1.0,
                 s_kv=math.sqrt(cfg.dim / a[2]) if on else 1.0)


def layer_shapes(cfg: Dots3Config, i: int) -> Dict[str, tuple]:
    """Layer `i`'s leaves: its attention form's, then its second half's."""
    kind = cfg.layer_types[i]
    a, D = attn_form(cfg, kind), cfg.dim
    out = {
        "attn_norm": (D,), "wq_a": (D, a.q_rank), "q_norm": (a.q_rank,),
        "wq_b": (a.q_rank, a.heads * (a.nope + a.rope)),
        "wkv_a": (D, a.latent), "kv_norm": (a.rank,),
        "wkv_b": (a.rank, a.heads * (a.nope + a.v)),
        "w_gate_attn": (D, a.heads), "wo": (a.heads * a.v, D),
    }
    if kind == FULL:
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        out.update({"idx_wq": (a.q_rank, Hi * di), "idx_wk": (D, di),
                    "idx_k_norm": (di,), "idx_k_bias": (di,),
                    "idx_ww": (D, Hi)})
    out["mlp_norm"] = (D,)
    if i < cfg.first_k_dense:
        I = cfg.intermediate
        out.update({"w_gate": (D, I), "w_up": (D, I), "w_down": (I, D)})
    else:
        E, Eh, Im = cfg.n_routed_experts, cfg.experts_held, cfg.moe_intermediate
        Is = cfg.n_shared_experts * Im
        out.update({"router": (D, E), "router_bias": (E,),
                    "e_gate": (Eh, D, Im), "e_up": (Eh, D, Im),
                    "e_down": (Eh, Im, D), "s_gate": (D, Is),
                    "s_up": (D, Is), "s_down": (Is, D)})
    return out


F32_LEAVES = ("router", "router_bias")


def init_params(cfg: Dots3Config, key: jax.Array, std: float = 0.02):
    """Random weights in the tree the functions below read: `tok_emb`,
    `final_norm`, `lm_head`, and `layers`, one dict a layer."""
    layers = []
    for i in range(cfg.n_layers):
        lk, leaves = jax.random.fold_in(key, i), {}
        for j, (name, shape) in enumerate(sorted(layer_shapes(cfg, i).items())):
            dt = F32 if name in F32_LEAVES else cfg.dtype
            if name.endswith("norm"):
                leaves[name] = jnp.ones(shape, dt)
            elif name == "idx_k_bias":
                leaves[name] = jnp.zeros(shape, dt)
            else:
                leaves[name] = (jax.random.normal(
                    jax.random.fold_in(lk, j), shape, F32) * std).astype(dt)
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


def leaf_index(cfg: Dots3Config, i: int) -> int:
    """Layer `i`'s place among the layers of its own kind: its row of
    the cache leaves that kind holds."""
    return sum(t == cfg.layer_types[i] for t in cfg.layer_types[:i])


# ----------------------------------------------------------------------
# parts
# ----------------------------------------------------------------------
def _layer_norm(x, g, b, eps):
    x32 = x.astype(F32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * g.astype(F32)
            + b.astype(F32)).astype(x.dtype)


def _qkv_latent(cfg, a: _Attn, layer, h, pos):
    """h [N, D] normed, pos [N] -> (q [N, H, rank + rope]: per head the
    query taken into the latent space beside its rotated rotary part;
    the row to cache [N, rank + rope]; c_q [N, q_rank])."""
    dt = cfg.dtype
    c_q = _rms_norm(_apply(h, layer["wq_a"], dt), layer["q_norm"].astype(dt),
                    cfg.norm_eps)
    c_q = (c_q.astype(F32) * a.s_q).astype(dt)
    q = _apply(c_q, layer["wq_b"], dt).reshape(-1, a.heads, a.nope + a.rope)
    q_rope = _rope_interleaved(q[..., a.nope:], a.theta, pos)
    kv_a = _apply(h, layer["wkv_a"], dt)
    c = _rms_norm(kv_a[..., :a.rank], layer["kv_norm"].astype(dt),
                  cfg.norm_eps)
    c = (c.astype(F32) * a.s_kv).astype(dt)
    k_rope = _rope_interleaved(kv_a[..., a.rank:], a.theta, pos)
    w_uk = layer["wkv_b"].astype(dt).reshape(a.rank, a.heads, a.nope + a.v)
    q_lat = jnp.einsum("nhd,chd->nhc", q[..., :a.nope], w_uk[..., :a.nope],
                       preferred_element_type=F32).astype(dt)
    return (jnp.concatenate([q_lat, q_rope], axis=-1),
            jnp.concatenate([c, k_rope], axis=-1), c_q)


def _index_inputs(cfg, layer, h, c_q, pos):
    """The indexer's side of a full layer: (qI [N, Hi, di], w [N, Hi]
    float32, kI [N, di] the row to cache)."""
    dt, Hi, di, r = cfg.dtype, cfg.index_n_heads, cfg.index_head_dim, \
        cfg.qk_rope_dim
    qI = _apply(c_q, layer["idx_wq"], dt).reshape(-1, Hi, di)
    qI = jnp.concatenate(
        [_rope_interleaved(qI[..., :r], cfg.rope_theta, pos), qI[..., r:]],
        axis=-1)
    kI = _layer_norm(_apply(h, layer["idx_wk"], dt), layer["idx_k_norm"],
                     layer["idx_k_bias"], cfg.norm_eps)
    kI = jnp.concatenate(
        [_rope_interleaved(kI[..., :r], cfg.rope_theta, pos), kI[..., r:]],
        axis=-1)
    w = (jnp.dot(h, layer["idx_ww"].astype(dt), preferred_element_type=F32)
         * (Hi ** -0.5 * di ** -0.5))
    return qI, w, kI


def _index_scores(qI, w, kI):
    """I[n, s] = sum_j w[n, j] relu(qI[n, j] . kI[.., s]): qI [N, Hi,
    di], w [N, Hi]; kI [M, di] (one key set for all queries) or [N, M,
    di] (each row its own) -> [N, M] float32."""
    eq = "njd,md->njm" if kI.ndim == 2 else "njd,nmd->njm"
    s = jnp.einsum(eq, qI, kI, preferred_element_type=F32)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=1)


def _attend_rows(a: _Attn, q, rows, valid, dtype):
    """Absorbed latent attention: q [N, H, latent]; rows [M, >= latent]
    (one key set) or [N, M, >= latent] (each query its own); valid [N,
    M] -> [N, H, rank] float32: the weighted sum of the rows' compressed
    part, softmax over the valid rows in float32."""
    rows = rows[..., :a.latent].astype(dtype)
    own = rows.ndim == 3
    s = jnp.einsum("nhd,nmd->nhm" if own else "nhd,md->nhm", q, rows,
                   preferred_element_type=F32)
    s = jnp.where(valid[:, None, :], s * a.scale, NEG)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("nhm,nmc->nhc" if own else "nhm,mc->nhc", p,
                      rows[..., :a.rank], preferred_element_type=F32)


def _out_of_latent(cfg, a: _Attn, layer, o_lat):
    """o_lat [N, H, rank] -> [N, H, v] float32: out of the latent space
    through `W_uv`."""
    dt = cfg.dtype
    w_uv = layer["wkv_b"].astype(dt).reshape(
        a.rank, a.heads, a.nope + a.v)[..., a.nope:]
    return jnp.einsum("nhc,chv->nhv", o_lat.astype(dt), w_uv,
                      preferred_element_type=F32)


def _gated_out(cfg, a: _Attn, layer, h, o):
    """o [N, H, v] float32 -> the half's output [N, D]: each head times
    its gate, `W_o`."""
    dt = cfg.dtype
    with jax.named_scope("attn_gate"):
        g = jax.nn.sigmoid(jnp.dot(h, layer["w_gate_attn"].astype(dt),
                                   preferred_element_type=F32))
        o = (o * g[..., None]).astype(dt)
    return _apply(o.reshape(o.shape[0], -1), layer["wo"], dt)


def _attn_out(cfg, a: _Attn, layer, h, o_lat):
    """o_lat [N, H, rank] -> the half's output [N, D]."""
    return _gated_out(cfg, a, layer, h, _out_of_latent(cfg, a, layer, o_lat))


def _select(scores, k: int, payload=None):
    """The `k` largest of each row of `scores` [N, M] (masked entries
    at `NEG`; of equal scores the earlier): (their indices [N, k], which
    of them are real [N, k]).  With `payload` [N, M] int32, no value
    twice in a row, it returns each chosen entry's payload in place of
    its index (of equal scores the lower payload): the sort that a
    top-k of this size is carries it for nothing, where a gather
    through the indices afterwards is 16k scalar reads a row group."""
    k = min(k, scores.shape[-1])
    if payload is None:
        vals, idx = lax.top_k(scores, k)
        return idx, vals > NEG / 2
    # both operands are keys: a total order, so two operands and no
    # third to keep the sort stable
    neg, pay = lax.sort((-scores, payload), dimension=-1, num_keys=2,
                        is_stable=False)
    return pay[:, :k], neg[:, :k] < -NEG / 2


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
    with jax.named_scope("moe_shared"):
        y = y + _swiglu(h, layer["s_gate"], layer["s_up"], layer["s_down"],
                        cfg.dtype)
    return x + y, stats


def _blocked(fn, xs, block: int):
    """`fn` over the leading axis of every array of `xs` in groups of
    `block` rows, one after the other (`lax.map`): what a group needs at
    once is all that is alive."""
    n = xs[0].shape[0]
    if n <= block or n % block:
        return fn(*xs)
    cut = jax.tree.map(
        lambda v: v.reshape((n // block, block) + v.shape[1:]), xs)
    out = lax.map(lambda g: fn(*g), cut)
    return jax.tree.map(lambda v: v.reshape((n,) + v.shape[2:]), out)


# ----------------------------------------------------------------------
# prefill: several sequences' next tokens, each behind its cached rows
# ----------------------------------------------------------------------
def _live_blocks(fn, n_live, xs, block: int, out_tail: tuple):
    """`fn(i, *group)` over the first `n_live` groups of `block` rows of
    every array of `xs`, one after the other in a loop whose TRIP COUNT
    is `n_live` (a traced scalar): a group past them costs nothing and
    its rows of the result stay zeros.  `fn` returns `[block,
    *out_tail]` float32.  Returns (the result, the trips made)."""
    n = xs[0].shape[0]

    def body(i, carry):
        out, trips = carry
        group = [lax.dynamic_slice_in_dim(v, i * block, block, 0) for v in xs]
        return (lax.dynamic_update_slice_in_dim(out, fn(i, *group),
                                                i * block, 0), trips + 1)

    return lax.fori_loop(0, n_live, body, (
        jnp.zeros((n,) + out_tail, F32), jnp.zeros((), jnp.int32)))


def forward_with_prefix(cfg: Dots3Config, params: Dict, tokens: jax.Array,
                        pos: jax.Array, cache, tables: jax.Array, *,
                        last=None, kernel: bool = False,
                        interpret: bool = False):
    """The next tokens of SEVERAL sequences in one pass over the
    weights, each behind its own cached rows: the decode step's form
    with many rows a sequence.  `tokens` [N]: the sequences' tokens end
    to end, each sequence's from a QUERY-BLOCK boundary (`N //
    tables.shape[0]` rows, whole cache blocks); `pos` [N] a token's
    position in its own sequence, -1 for padding; `cache` the three
    paged pools as `decode_step` takes them; `tables` [query blocks, W]
    each query block's sequence's row of the block table, which has to
    name the blocks of every position up to the block's last.  A
    sequence's first position here starts a cache block.

    A layer WRITES its new rows into the sequences' own blocks first,
    whole blocks at a time (a block's rows past the sequence's last
    token are written too: whatever padding computed, which the
    sequence's `pos` masks until decoding overwrites them; a block of
    padding alone is written nowhere), then attends THROUGH THE TABLE,
    the new rows with the cached ones: a full layer scores a query
    block's index keys over its whole table, selects, and gathers the
    selected rows from a copy of the table's blocks laid side by side;
    a window layer reads the blocks the block's window can touch.  Only the LIVE query blocks
    (those that hold a token; they come first) are walked: a padded
    block costs no score, no sort and no gather.  Projections, gates,
    experts and norms run once on the `[N, D]` rows, padding routed to
    no expert.

    Returns (logits float32 of the rows `last` [K] names, or of every
    row; the cache; `{"query_blocks": the blocks the layers walked,
    summed}`).  A sequence alone, from position 0 or behind a cached
    prefix or a chunk of a long prompt behind those before it, is a
    pack of one."""
    dt = cfg.dtype
    lat_pool, kI_pool, swa_pool = cache
    NB, BS = lat_pool.shape[1:3]
    N, (NQ, W) = tokens.shape[0], tables.shape
    QB = N // NQ
    if N % NQ or QB % BS:
        raise ValueError(f"{N} rows in {NQ} query blocks of whole cache "
                         f"blocks of {BS}")
    real = pos >= 0
    n_live = jnp.sum(real[::QB]).astype(jnp.int32)
    # the cache block each block of BS rows is written to; padding: none
    first = pos[::BS]
    wblk = jnp.take_along_axis(
        tables[jnp.arange(N // BS) * BS // QB],
        jnp.clip(first // BS, 0, W - 1)[:, None], axis=1)[:, 0]
    wblk = jnp.where(first >= 0, wblk, NB)
    pos = jnp.maximum(pos, 0)
    kpos = jnp.arange(W * BS, dtype=jnp.int32)
    # a window layer's blocks: from the one a query block's oldest
    # position lies in to the one its newest does
    WB = min(-(-(cfg.window - 2 + QB) // BS) + 1, W)

    def write(pool, li, new):
        new = jnp.pad(new, ((0, 0), (0, pool.shape[-1] - new.shape[-1])))
        return pool.at[li, wblk].set(
            new.reshape(N // BS, BS, -1).astype(pool.dtype), mode="drop")

    x = _embed(params, tokens, dt).astype(dt)
    walked = jnp.zeros((), jnp.int32)

    for i, layer in enumerate(params["layers"]):
        kind = cfg.layer_types[i]
        a, li = attn_form(cfg, kind), leaf_index(cfg, i)
        h = _rms_norm(x, layer["attn_norm"].astype(dt), cfg.norm_eps)
        q, new, c_q = _qkv_latent(cfg, a, layer, h, pos)
        if kind == FULL:
            with jax.named_scope("dsa_index"):
                qI, w, kI = _index_inputs(cfg, layer, h, c_q, pos)
                kI_pool = write(kI_pool, li, kI)
            lat_pool = write(lat_pool, li, new)
            keys_flat = kI_pool.reshape((-1,) + kI_pool.shape[2:])
            rows_flat = lat_pool.reshape((-1,) + lat_pool.shape[2:])

            def full(b, q, qI, w, p, li=li, a=a, layer=layer,
                     keys_flat=keys_flat, rows_flat=rows_flat):
                # the sequence's own blocks, side by side: 22 MB of rows
                # at 17k positions, which the selected rows are then
                # gathered FROM (a quarter of the time a gather from
                # the whole pool takes: the copy fits the fast memory)
                tab = li * NB + tables[b]
                with jax.named_scope("dsa_index"):
                    keys = keys_flat[tab].reshape(W * BS, -1)
                    sc = _index_scores(qI, w, keys.astype(dt))
                    sc = jnp.where(kpos[None, :] <= p[:, None], sc, NEG)
                with jax.named_scope("dsa_select"):
                    idx, picked = _select(sc, cfg.index_topk)
                with jax.named_scope("dsa_attn"):
                    rows = rows_flat[tab][..., :a.latent].reshape(
                        W * BS, -1)
                    return _out_of_latent(cfg, a, layer, _attend_rows(
                        a, q, rows[idx], picked, dt))

            o, trips = _live_blocks(full, n_live, (q, qI, w, pos), QB,
                                    (a.heads, a.v))
        else:
            swa_pool = write(swa_pool, li, new)
            pages = swa_pool.reshape((-1,) + swa_pool.shape[2:])

            def swa(b, q, p, li=li, a=a, layer=layer, pages=pages):
                b0 = jnp.maximum(p[0] - (cfg.window - 1), 0) // BS
                wtab = tables[b][jnp.clip(b0 + jnp.arange(WB), 0, W - 1)]
                d = p[:, None] - (b0 * BS + jnp.arange(WB * BS))[None, :]
                with jax.named_scope("swa_attn"):
                    rows = pages[li * NB + wtab].reshape(WB * BS, -1)
                    return _out_of_latent(cfg, a, layer, _attend_rows(
                        a, q, rows, (d >= 0) & (d < cfg.window), dt))

            o, trips = _live_blocks(swa, n_live, (q, pos), QB,
                                    (a.heads, a.v))
        walked = walked + trips
        x = x + _gated_out(cfg, a, layer, h, o)
        x, _ = _ffn(cfg, layer, x, kernel=kernel, interpret=interpret,
                    row_mask=real)
    if last is not None:
        x = x[last]
    x = _rms_norm(x, params["final_norm"].astype(dt), cfg.norm_eps)
    return (_lm_head(x, params, dt), (lat_pool, kI_pool, swa_pool),
            {"query_blocks": walked})


def forward(cfg: Dots3Config, params: Dict, tokens: jax.Array, **kw):
    """tokens [T] -> logits [T, vocab] float32: the whole sequence in
    one program, through a cache of its own (tests; a serving prompt
    goes through the engine's pool, chunk by chunk)."""
    T = tokens.shape[0]
    QB = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    BS = math.gcd(T, 8)
    nb = T // BS
    cache = tuple(
        jnp.zeros((n, nb + 1, BS, d), cfg.dtype) for n, d in (
            (cfg.n_full_layers, cfg.latent_dim),
            (cfg.n_full_layers, cfg.index_head_dim),
            (cfg.n_swa_layers, cfg.swa_latent_dim)))
    tables = jnp.broadcast_to(jnp.arange(1, nb + 1, dtype=jnp.int32),
                              (T // QB, nb))
    return forward_with_prefix(cfg, params, tokens,
                               jnp.arange(T, dtype=jnp.int32), cache,
                               tables, **kw)[0]


# ----------------------------------------------------------------------
# decode: one step through the paged cache
# ----------------------------------------------------------------------
def _scatter_rows(pool, li, blk, off, new):
    """One new row a sequence into `pool` [L, NB, BS, Dp] at (layer
    `li`, block `blk` [B], offset `off` [B]); a block id of NB (a dead
    row) is dropped."""
    new = jnp.pad(new, ((0, 0), (0, pool.shape[-1] - new.shape[-1])))
    return pool.at[li, blk, off].set(new.astype(pool.dtype), mode="drop")


def decode_step(cfg: Dots3Config, params: Dict, token: jax.Array, cache,
                pos, tables, *, live=None, kernel: bool = False,
                interpret: bool = False):
    """One decode step at per-row positions: token [B], pos [B], `cache`
    = `(latent, index_k, swa_latent)` paged pools `[layers of the kind,
    NB, BS, Dp]`, `tables` [B, W] each row's blocks.  Every layer
    writes its row into its pool at `pos` and attends through the
    table: a full layer over the `index_topk` rows its indexer picks
    from positions `0..pos`, a window layer over the last `window`
    positions.  Returns (logits [B, vocab] float32, cache, stats) with
    `stats` = `experts_touched`, `load_max` over the HELD experts.

    `live` [B] bool (the engine's `pos < stop`; None: every row): a row
    that is not live writes nothing (it may still hold a table whose
    blocks a cached prefix shares) and is routed to no expert; what it
    attends is nobody's."""
    dt = cfg.dtype
    lat_pool, kI_pool, swa_pool = cache
    NB, BS = lat_pool.shape[1:3]
    B, W = tables.shape
    blk = jnp.take_along_axis(
        tables, jnp.clip(pos // BS, 0, W - 1)[:, None], axis=1)[:, 0]
    if live is not None:
        blk = jnp.where(live, blk, NB)
    off = pos % BS
    # a window layer's blocks: from the one its oldest position lies in
    WB = min(cfg.window_blocks(BS), W)
    lo = jnp.maximum(pos - (cfg.window - 1), 0)
    b0 = lo // BS
    wtab = jnp.take_along_axis(
        tables, jnp.clip(b0[:, None] + jnp.arange(WB)[None, :], 0, W - 1),
        axis=1)                                             # [B, WB]
    wkpos = (b0 * BS)[:, None] + jnp.arange(WB * BS)[None, :]
    wok = (wkpos <= pos[:, None]) & (wkpos >= lo[:, None])
    x = _embed(params, token, dt).astype(dt)                # [B, D]
    touched = jnp.zeros((), jnp.int32)
    load_max = jnp.zeros((), jnp.int32)

    for i, layer in enumerate(params["layers"]):
        kind = cfg.layer_types[i]
        a, li = attn_form(cfg, kind), leaf_index(cfg, i)
        h = _rms_norm(x, layer["attn_norm"].astype(dt), cfg.norm_eps)
        q, new, c_q = _qkv_latent(cfg, a, layer, h, pos)
        if kind == FULL:
            with jax.named_scope("dsa_index"):
                qI, w, kI = _index_inputs(cfg, layer, h, c_q, pos)
                kI_pool = _scatter_rows(kI_pool, li, blk, off, kI)
            lat_pool = _scatter_rows(lat_pool, li, blk, off, new)
            keys_flat = kI_pool.reshape((-1,) + kI_pool.shape[2:])
            rows_flat = lat_pool.reshape((-1, lat_pool.shape[-1]))

            def full(q, qI, w, p, tab, li=li, a=a, keys_flat=keys_flat,
                     rows_flat=rows_flat):
                with jax.named_scope("dsa_index"):
                    keys = keys_flat[li * NB + tab]       # [n, W, BS, di]
                    keys = keys.reshape(keys.shape[0], W * BS, -1)
                    sc = _index_scores(qI, w, keys.astype(dt))
                    sc = jnp.where(jnp.arange(W * BS)[None, :] <= p[:, None],
                                   sc, NEG)
                with jax.named_scope("dsa_select"):
                    # every position's row of the flat pool, built from
                    # the table by a broadcast, rides the sort
                    flat = (((tab + li * NB) * BS)[:, :, None]
                            + jnp.arange(BS)[None, None, :])
                    flat, real = _select(sc, cfg.index_topk,
                                         flat.reshape(flat.shape[0], -1))
                with jax.named_scope("dsa_attn"):
                    return _attend_rows(a, q, rows_flat[flat], real, dt)

            o_lat = _blocked(full, (q, qI, w, pos, tables), ROW_GROUP)
        else:
            with jax.named_scope("swa_attn"):
                swa_pool = _scatter_rows(swa_pool, li, blk, off, new)
                pages = swa_pool.reshape((-1,) + swa_pool.shape[2:])
                rows = pages[li * NB + wtab]               # [B, WB, BS, Dp]
                rows = rows.reshape(B, WB * BS, -1)
                o_lat = _attend_rows(a, q, rows, wok, dt)
        x = x + _attn_out(cfg, a, layer, h, o_lat)
        x, stats = _ffn(cfg, layer, x, kernel=kernel, interpret=interpret,
                        row_mask=live)
        if stats is not None:
            touched = touched + stats["experts_touched"]
            load_max = jnp.maximum(load_max, stats["load_max"])
    x = _rms_norm(x, params["final_norm"].astype(dt), cfg.norm_eps)
    return (_lm_head(x, params, dt), (lat_pool, kI_pool, swa_pool),
            {"experts_touched": touched, "load_max": load_max})
