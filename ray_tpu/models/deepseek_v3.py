"""DeepSeek-V3-lineage decoder for SERVING: multi-head latent attention
(MLA) and a dropless mixture of experts with shared experts.

The architecture of `model_type: deepseek_v3` checkpoints without a
query low-rank (`q_lora_rank` null), e.g. kakaocorp's kanana-2-30b-a3b
(DeepSeek-AI, "DeepSeek-V3 Technical Report", arXiv:2412.19437; MLA
from "DeepSeek-V2", arXiv:2405.04434).  Pre-norm RMSNorm, residual
around each half; the first `first_k_dense` layers carry a dense SwiGLU
MLP, every later layer a routed expert layer plus an always-on shared
expert.

What the serve engine needs of a model, and nothing else (no training
step here; the expert layer that trains is `parallel/moe.py`'s capacity
form under `models/mixtral.py`):

- `forward(..., return_kv=True)`: prefill in the EXPANDED form — the
  compressed KV `c` goes through `W_kvb` to per-head keys and values and
  attention is ordinary causal multi-head attention of width 192 / 128.
  What it returns to be cached is NOT per head: one latent row a token
  and layer, `[c (after its norm) | k_rope (after rotary)]`, 576 values.
- `forward_with_prefix`: suffix prefill over cached latents, which are
  expanded through `W_kvb` as prefill expands its own.
- `decode_step`: the ABSORBED form — `W_kvb` splits per head into
  `W_uk` and `W_uv`; the query is taken into the latent space
  (`q_nope W_uk`), scores and the weighted sum run on the cached rows
  as they lie (`ops/paged_attention.mla_paged_decode_attention`: one
  pool, read once, the value is the row's first 512 columns), and the
  result leaves it through `W_uv`.  Same mathematics as the expanded
  form (`tests/test_deepseek_v3.py` holds the two together).

Rotary: `rope_interleave` pairs dims (2i, 2i + 1) of the 64 rotary
dims.  Here, as in the published implementation, the vector is first
de-interleaved to [evens | odds] and rotated in halves; queries and the
shared key take the same permutation, so every score equals the one
the in-place pair rotation gives (the plain reference under
`benchmarks/reference/` rotates in place).

`jax.named_scope`s `mla_attn`, `dense_mlp`, `moe_router`, `moe_routed`,
`moe_shared` mark the parts in a device trace.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import (Packed, _apply, _embed, _lm_head,
                                  _rms_norm)
from ray_tpu.ops import paged_attention as _pa
from ray_tpu.parallel.moe import dropless_moe


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    max_seq_len: int = 32768
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate: int = 6144          # the dense layers' SwiGLU
    moe_intermediate: int = 768       # one routed expert's SwiGLU
    n_routed_experts: int = 128
    n_shared_experts: int = 2         # one SwiGLU of that many widths
    top_k: int = 6
    first_k_dense: int = 1
    routed_scale: float = 2.448
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention: str = "dense"          # what the engine's prefix cache asks

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_dim(self) -> int:
        """Values cached a token and layer: compressed KV + rotary key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @staticmethod
    def tiny(vocab_size: int = 256) -> "DeepseekV3Config":
        return DeepseekV3Config(
            vocab_size=vocab_size, max_seq_len=128, dim=64, n_layers=3,
            n_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
            kv_lora_rank=16, intermediate=128, moe_intermediate=32,
            n_routed_experts=8, n_shared_experts=2, top_k=2,
            first_k_dense=1, dtype=jnp.float32)


def layer_shapes(cfg: DeepseekV3Config) -> Dict[str, Dict[str, tuple]]:
    """One layer's leaves, for a dense and for an expert layer."""
    D, H = cfg.dim, cfg.n_heads
    attn = {
        "attn_norm": (D,), "wq": (D, H * cfg.qk_head_dim),
        "wkv_a": (D, cfg.latent_dim), "kv_norm": (cfg.kv_lora_rank,),
        "wkv_b": (cfg.kv_lora_rank, H * (cfg.qk_nope_dim + cfg.v_head_dim)),
        "wo": (H * cfg.v_head_dim, D), "mlp_norm": (D,),
    }
    I, Im, E = cfg.intermediate, cfg.moe_intermediate, cfg.n_routed_experts
    Is = cfg.n_shared_experts * Im
    return {
        "dense": {**attn, "w_gate": (D, I), "w_up": (D, I),
                  "w_down": (I, D)},
        "moe": {**attn, "router": (D, E), "router_bias": (E,),
                "e_gate": (E, D, Im), "e_up": (E, D, Im),
                "e_down": (E, Im, D), "s_gate": (D, Is), "s_up": (D, Is),
                "s_down": (Is, D)},
    }


# leaves kept in float32 whatever the compute dtype: the router scores
# decide WHICH experts run, and a bfloat16 score flips near-ties
F32_LEAVES = ("router", "router_bias")
# the published router's normaliser: `topk_weights / (sum + 1e-20)`
ROUTE_EPS = 1e-20


def init_params(cfg: DeepseekV3Config, key: jax.Array, std: float = 0.02):
    """Random weights in the tree the functions below read: `tok_emb`,
    `final_norm`, `lm_head`, and two stacks, `dense_layers` (the first
    `first_k_dense`) and `moe_layers` (the rest), each leaf
    `[layers, ...]`.  Two stacks, not one: their leaves differ, and a
    program that sliced one stack would copy the slice every step."""
    shapes = layer_shapes(cfg)

    def stack(kind, n, key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes[kind].items())):
            dt = jnp.float32 if name in F32_LEAVES else cfg.dtype
            if name.endswith("norm"):
                out[name] = jnp.ones((n,) + shape, dt)
            else:
                out[name] = (jax.random.normal(
                    jax.random.fold_in(key, i), (n,) + shape, jnp.float32)
                    * std).astype(dt)
        return out

    k = jax.random.split(key, 4)
    return {
        "tok_emb": (jax.random.normal(k[0], (cfg.vocab_size, cfg.dim))
                    * std).astype(cfg.dtype),
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": (jax.random.normal(k[1], (cfg.dim, cfg.vocab_size))
                    * std).astype(cfg.dtype),
        "dense_layers": stack("dense", cfg.first_k_dense, k[2]),
        "moe_layers": stack("moe", cfg.n_moe_layers, k[3]),
    }


# ----------------------------------------------------------------------
# parts
# ----------------------------------------------------------------------
def _rope_interleaved(x, theta: float, pos):
    """x [..., T, n, d] (or [..., T, d] with `pos` [..., T]) rotary with
    interleaved pairs: de-interleave to [evens | odds], rotate halves.
    `pos` broadcasts against x's leading dims up to T."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x.ndim:  # a heads axis between T and d
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _swiglu(h, gate, up, down, dtype):
    return _apply(jax.nn.silu(_apply(h, gate, dtype)) * _apply(h, up, dtype),
                  down, dtype)


def _qkv_latent(cfg, layer, x, pos):
    """The projections every form shares: x [..., T, D], pos [..., T] ->
    (q_nope [..., T, H, 128], q_rope [..., T, H, 64] rotated,
    latent [..., T, 576] = [normalised c | rotated k_rope])."""
    H = cfg.n_heads
    h = _rms_norm(x, layer["attn_norm"].astype(cfg.dtype), cfg.norm_eps)
    q = _apply(h, layer["wq"], cfg.dtype)
    q = q.reshape(q.shape[:-1] + (H, cfg.qk_head_dim))
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = _rope_interleaved(q_rope, cfg.rope_theta, pos)
    kv_a = _apply(h, layer["wkv_a"], cfg.dtype)
    c = _rms_norm(kv_a[..., :cfg.kv_lora_rank],
                  layer["kv_norm"].astype(cfg.dtype), cfg.norm_eps)
    k_rope = _rope_interleaved(kv_a[..., cfg.kv_lora_rank:],
                               cfg.rope_theta, pos)
    return q_nope, q_rope, jnp.concatenate([c, k_rope], axis=-1)


def _expand(cfg, layer, latent):
    """Cached rows -> per-head keys and values through `W_kvb`:
    latent [B, T, 576] -> (k [B, T, H, 192], v [B, T, H, 128])."""
    H, n = cfg.n_heads, cfg.qk_nope_dim
    kv = _apply(latent[..., :cfg.kv_lora_rank], layer["wkv_b"], cfg.dtype)
    kv = kv.reshape(kv.shape[:-1] + (H, n + cfg.v_head_dim))
    k_rope = jnp.broadcast_to(
        latent[..., None, cfg.kv_lora_rank:],
        kv.shape[:-1] + (cfg.qk_rope_dim,))
    return jnp.concatenate([kv[..., :n], k_rope], axis=-1), kv[..., n:]


def _attend_expanded(cfg, q, k, v, mask):
    """q [B, S, H, 192], k [B, M, H, 192], v [B, M, H, 128], mask
    [S, M] bool -> [B, S, H * 128]; softmax in float32."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(mask[None, None], s * cfg.qk_head_dim ** -0.5, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   preferred_element_type=jnp.float32)
    return o.astype(cfg.dtype).reshape(o.shape[:2] + (-1,))


def _ffn(cfg, layer, x, *, kernel=False, interpret=False, stack_index=None,
         row_mask=None):
    """The second half of a layer: dense SwiGLU, or routed experts plus
    the shared expert.  x [..., D] -> (x + y, stats or None).
    `row_mask` (one bool a row of the flattened x): see `dropless_moe`."""
    h = _rms_norm(x, layer["mlp_norm"].astype(cfg.dtype), cfg.norm_eps)
    if "router" not in layer:
        with jax.named_scope("dense_mlp"):
            return x + _swiglu(h, layer["w_gate"], layer["w_up"],
                               layer["w_down"], cfg.dtype), None
    flat = h.reshape(-1, h.shape[-1])
    y, stats = dropless_moe(flat, layer, top_k=cfg.top_k,
                            scale=cfg.routed_scale, route_eps=ROUTE_EPS,
                            dtype=cfg.dtype,
                            kernel=kernel, interpret=interpret,
                            stack_index=stack_index, row_mask=row_mask)
    with jax.named_scope("moe_shared"):
        y = y.reshape(h.shape) + _swiglu(h, layer["s_gate"], layer["s_up"],
                                         layer["s_down"], cfg.dtype)
    return x + y, stats


EXPERT_LEAVES = ("e_gate", "e_up", "e_down")


def _stacks(params):
    """Per non-empty stack (dense layers, expert layers): (leaves a
    layer scan slices, expert leaves it must NOT slice, first layer
    index, layers).  The
    routed experts' stacks stay whole beside the scan: the grouped
    kernel picks its layer by index (`moe.grouped_matmul`)."""
    out = []
    l0 = 0
    for stack in (params["dense_layers"], params["moe_layers"]):
        experts = {k: stack[k] for k in EXPERT_LEAVES if k in stack}
        scanned = {k: v for k, v in stack.items() if k not in experts}
        n = stack["attn_norm"].shape[0]
        if n:
            out.append((scanned, experts, l0, n))
        l0 += n
    return out


# ----------------------------------------------------------------------
# prefill (expanded form)
# ----------------------------------------------------------------------
def forward(cfg: DeepseekV3Config, params: Dict, tokens: jax.Array,
            return_kv: bool = False, *, kernel: bool = False,
            interpret: bool = False, packed: Optional[Packed] = None):
    """tokens [B, T] -> logits [B, T, vocab] float32; with `return_kv`
    also the latents to cache, [L, B, T, 576].  Right-padding changes
    no real token's result: attention is causal and the expert layer
    drops nothing.  `packed`: see `forward_with_prefix`."""
    return forward_with_prefix(cfg, params, tokens, None, 0,
                               return_kv=return_kv, kernel=kernel,
                               interpret=interpret, packed=packed)


def forward_with_prefix(cfg: DeepseekV3Config, params: Dict,
                        tokens: jax.Array, prefix, prefix_len, *,
                        return_kv: bool = True, kernel: bool = False,
                        interpret: bool = False,
                        packed: Optional[Packed] = None):
    """Suffix forward over cached latents: `tokens` [B, S] live at
    positions `prefix_len`..; `prefix` [L, B, Pmax, >= 576] are the
    gathered rows of the shared prefix (columns at or past
    `prefix_len` masked), expanded through `W_kvb` like the suffix's
    own.  `prefix` None is the full prefill.  Returns (logits [B, S,
    vocab], suffix latents [L, B, S, 576]).

    `packed` (full prefill, B == 1; `llama.Packed`): the row holds
    several prompts end to end.  Its `pos` and `seg` take the place of
    the positions and the mask above, a padding token (`seg` < 0) is
    routed to no expert, and the logits are `[B, K, vocab]`, the rows
    `packed.last` only."""
    B, S = tokens.shape
    Pmax = 0 if prefix is None else prefix.shape[2]
    pos = prefix_len + jnp.arange(S)
    cols = jnp.arange(Pmax + S)
    mask = ((cols[None, :] < jnp.minimum(prefix_len, Pmax))
            | ((cols[None, :] >= Pmax)
               & (cols[None, :] - Pmax <= jnp.arange(S)[:, None])))
    real = None  # every row routes
    if packed is not None and packed.seg is not None:
        pos, mask, real = packed.pos, packed.mask(), packed.seg >= 0
    x = _embed(params, tokens, cfg.dtype).astype(cfg.dtype)

    def body(experts, x, inputs):
        i, layer, pre = inputs
        with jax.named_scope("mla_attn"):
            q_nope, q_rope, latent = _qkv_latent(cfg, layer, x, pos[None])
            rows = latent if pre is None else jnp.concatenate(
                [pre[..., :cfg.latent_dim].astype(cfg.dtype), latent], axis=1)
            k, v = _expand(cfg, layer, rows)
            o = _attend_expanded(
                cfg, jnp.concatenate([q_nope, q_rope], axis=-1), k, v, mask)
            x = x + _apply(o, layer["wo"], cfg.dtype)
        x, _ = _ffn(cfg, {**layer, **experts}, x, kernel=kernel,
                    interpret=interpret, stack_index=i, row_mask=real)
        return x, latent

    kvs = []
    for scanned, experts, l0, n in _stacks(params):
        pre = None if prefix is None else prefix[l0:l0 + n]
        x, kv = lax.scan(
            functools.partial(body, experts), x,
            (jnp.arange(n, dtype=jnp.int32), scanned, pre))
        kvs.append(kv)
    if packed is not None:
        x = x[:, packed.last]  # the head reads K rows, not S
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    logits = _lm_head(x, params, cfg.dtype)
    if not return_kv:
        return logits
    return logits, jnp.concatenate(kvs, axis=0)


# ----------------------------------------------------------------------
# decode (absorbed form)
# ----------------------------------------------------------------------
def _absorbed_dense(cfg, q, rows, pos):
    """The latent kernel's arithmetic in plain XLA on a dense cache
    (the engine's gather route: CPU tests and the reference for the
    kernel): q [B, H, 576], rows [B, M, >= 576], pos [B] ->
    [B, H, 512]."""
    rows = rows[..., :cfg.latent_dim].astype(cfg.dtype)
    s = jnp.einsum("bhd,bmd->bhm", q, rows,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, :], s * cfg.qk_head_dim ** -0.5, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bhm,bmc->bhc", w, rows[..., :cfg.kv_lora_rank],
                      preferred_element_type=jnp.float32)


def decode_step(cfg: DeepseekV3Config, params: Dict, token: jax.Array,
                cache, pos, *, tables=None, live=None,
                kernel: bool = False, interpret: bool = False):
    """One decode step at per-row positions.  token [B], pos [B].

    `tables` given: `cache` is the paged latent pool `[L, NB, BS, Dp]`
    and each layer appends its row and attends through the Pallas
    kernels.  `tables` None: `cache` is a dense `[L, B, M, Dp]` view
    (the engine's gather route), written by a masked select.  Either
    way the attention is the absorbed form.  Returns (logits [B,
    vocab] float32, cache, stats) with `stats` = `experts_touched`
    (distinct (layer, expert) pairs this step) and `load_max` (most
    rows any one expert got).

    `live` [B] bool (the engine's `pos < stop`): a row that is not live
    writes nothing into the cache, attends nothing on the paged route
    (`dead_row_positions`) and is routed to no expert, so `stats`
    count live rows only."""
    H, n, r = cfg.n_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    x = _embed(params, token, cfg.dtype).astype(cfg.dtype)  # [B, D]
    if tables is None:
        M = cache.shape[2]
        write = jnp.arange(M)[None, :] == pos[:, None]
        if live is not None:
            write = write & live[:, None]
        write = write[:, :, None]
    else:  # where a row appends, how far it attends
        w_pos, a_pos = _pa.dead_row_positions(pos, live, tables,
                                              cache.shape[2])

    def body(experts, l0, carry, inputs):
        x, cache = carry
        i, layer = inputs
        li = l0 + i
        with jax.named_scope("mla_attn"):
            q_nope, q_rope, new = _qkv_latent(cfg, layer, x, pos)
            w_kvb = layer["wkv_b"].astype(cfg.dtype).reshape(
                r, H, n + cfg.v_head_dim)
            q_lat = jnp.einsum("bhn,chn->bhc", q_nope, w_kvb[..., :n],
                               preferred_element_type=jnp.float32)
            q = jnp.concatenate([q_lat.astype(cfg.dtype), q_rope], axis=-1)
            if tables is not None:
                cache = _pa.mla_paged_kv_append(
                    cache, new.astype(cache.dtype), tables, w_pos, li,
                    interpret=interpret)
                o_lat = _pa.mla_paged_decode_attention(
                    q, cache, tables, a_pos, li, value_dim=r,
                    scale=cfg.qk_head_dim ** -0.5, interpret=interpret)
            else:
                new = jnp.pad(new, ((0, 0), (0, cache.shape[-1]
                                             - new.shape[-1])))
                rows = jnp.where(write, new[:, None].astype(cache.dtype),
                                 cache[li])
                cache = lax.dynamic_update_index_in_dim(cache, rows, li, 0)
                o_lat = _absorbed_dense(cfg, q, rows, pos)
            o = jnp.einsum("bhc,chv->bhv", o_lat.astype(cfg.dtype),
                           w_kvb[..., n:],
                           preferred_element_type=jnp.float32)
            x = x + _apply(o.astype(cfg.dtype).reshape(o.shape[0], -1),
                           layer["wo"], cfg.dtype)
        x, stats = _ffn(cfg, {**layer, **experts}, x, kernel=kernel,
                        interpret=interpret, stack_index=i, row_mask=live)
        return (x, cache), stats

    touched = jnp.zeros((), jnp.int32)
    load_max = jnp.zeros((), jnp.int32)
    for scanned, experts, l0, count in _stacks(params):
        (x, cache), stats = lax.scan(
            functools.partial(body, experts, l0), (x, cache),
            (jnp.arange(count, dtype=jnp.int32), scanned))
        if stats is not None:
            touched = touched + jnp.sum(stats["experts_touched"])
            load_max = jnp.maximum(load_max, jnp.max(stats["load_max"]))
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    logits = _lm_head(x, params, cfg.dtype)
    return logits, cache, {"experts_touched": touched, "load_max": load_max}
