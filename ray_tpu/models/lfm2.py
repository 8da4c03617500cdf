"""LFM2-MoE-lineage decoder for SERVING: a HYBRID of layer kinds.  Most
layers mix tokens with a gated SHORT CONVOLUTION (three causal taps, a
state of fixed size a sequence), a few with grouped-query softmax
attention (K and V rows that grow with the context), and behind the
first `n_dense_layers` dense SwiGLU layers every layer's second half is
a sigmoid-routed mixture of experts with no shared expert.

The architecture of `model_type: lfm2_moe` checkpoints (Liquid AI,
`LFM2-8B-A1B`; the published `config.json` and the family's modelling
code).  A layer, on `x [T, D]`:

    h = x + op(RMSNorm(x; operator_norm));  y = h + ffn(RMSNorm(h; ffn_norm))

- `op` of a `conv` layer, on the normed input `x'`: `(B, C, u) =
  split3(x' W_in)`, `W_in [D, 3 D]`; `z_t = sum_k w[:, k] (B * u)_{t - 2
  + k}` for `k` = 0..2 (depthwise, causal, zeros before the sequence's
  first token, no bias); `op = (C * z) W_out`.
- `op` of a `full_attention` layer: q / k / v projections without bias,
  an RMS norm over the `head_dim` values of every q and k head (gains
  `q_norm`, `k_norm`), rotary over the whole head (halves), causal
  softmax attention at scale `head_dim ** -0.5`, query head `h` through
  KV head `h // (H // KV)`, the output projection.
- `ffn`: `W2 (silu(W1 x) * W3 x)` in the dense layers; in the others `s
  = sigmoid(x W_g)` in float32, the `top_k` largest of `s +
  expert_bias` chosen, weighted by `s` alone over `sum + 1e-6`
  (`parallel/moe.dropless_moe`).
- after the last layer `RMSNorm(.; embedding_norm)`, then the head,
  which is the embedding transposed (tied).

What the serve engine needs of a model, and nothing else:

- `forward`: prefill over a PACKED row (`llama.Packed`): attention
  inside a prompt only, and a convolution tap is taken only where the
  token's position inside its own prompt reaches back that far (`posn
  >= 2 - k`), so prompts that lie end to end never leak.  It returns
  the attention layers' K and V rows for the pool and leaves each
  prompt's convolution state in its slot.
- `decode_step`: one token for every live row: the attention layers
  append to and read the paged pool (or its dense view), the
  convolution layers roll their slot's state; a dead row leaves both
  alone.

THE CACHE IS BOTH KINDS AT ONCE (`serve/engine_model.py`): `k`, `v`
`[attn_layers, num_blocks, block_size, KV, hd]` paged (on the device a
token's 64-wide heads side by side in one row of whole lanes, `[.., KV *
hd]`, the same bytes: `ops/paged_attention.kv_pool_tail`), and `conv`
`[conv_layers, slots, conv_L * D]` per slot: the last `conv_L` values
of `B * u`, tap-major (columns `k D .. (k + 1) D` hold the value `2 -
k` tokens back), in the compute dtype: a copy of activations, not an
accumulator.  The published state is `[D, conv_L]` a sequence; with 3
as the minor dimension a TPU would pad every row of it to 128 lanes, so
the same 6,144 values lie flat.

Layers of one kind are STACKED (`conv`, `attn`, `dense`, `moe`), and
the layers run as RUNS of consecutive layers of one (operator, ffn)
pair, each a `lax.scan` over indices into stacks that stay whole beside
it: a scan's `xs` cut out of a stack would be copied every step, and
the expert stacks go to the grouped kernel whole with the layer's index
(`moe.grouped_matmul`).  `jax.named_scope`s `short_conv`, `gqa_attn`,
`dense_mlp`, `moe_router`, `moe_routed` mark the parts in a trace.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import (Packed, _apply, _embed, _rms_norm, _rope,
                                  _rope_at)
from ray_tpu.ops import paged_attention as _pa
from ray_tpu.parallel.moe import dropless_moe

F32 = jnp.float32
CONV, ATTN = "conv", "full_attention"
# the published order of the 24 layers
LAYER_TYPES = ((CONV, CONV, ATTN) + (CONV, CONV, CONV, ATTN) * 4
               + (CONV, CONV, ATTN, CONV, CONV))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    max_seq_len: int = 128000
    dim: int = 2048
    layer_types: Tuple[str, ...] = LAYER_TYPES
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    intermediate: int = 7168          # the dense layers' SwiGLU
    moe_intermediate: int = 1792      # one expert's SwiGLU
    n_experts: int = 32
    top_k: int = 4
    n_dense_layers: int = 2
    routed_scale: float = 1.0
    conv_L: int = 3                   # taps = values of `B * u` kept
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    route_eps: float = 1e-6           # the router's `sum + eps`
    dtype: Any = jnp.bfloat16
    attention: str = "dense"          # what the engine's packed prefill asks

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_attn_layers(self) -> int:
        return sum(t == ATTN for t in self.layer_types)

    @property
    def n_conv_layers(self) -> int:
        return self.n_layers - self.n_attn_layers

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @staticmethod
    def tiny(vocab_size: int = 256) -> "Lfm2MoeConfig":
        return Lfm2MoeConfig(
            vocab_size=vocab_size, max_seq_len=128, dim=64,
            layer_types=(CONV, CONV, ATTN, CONV, CONV, ATTN), n_heads=4,
            n_kv_heads=2, head_dim=16, intermediate=128, moe_intermediate=32,
            n_experts=8, top_k=2, n_dense_layers=2, dtype=jnp.float32)


def layer_shapes(cfg: Lfm2MoeConfig) -> Dict[str, Dict[str, tuple]]:
    """One layer's leaves, by stack: the two operators, the two ffns."""
    D, H, KV, d = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    I, Im, E = cfg.intermediate, cfg.moe_intermediate, cfg.n_experts
    return {
        "conv": {"op_norm": (D,), "w_in": (D, 3 * D),
                 "conv_w": (D, cfg.conv_L), "w_out": (D, D)},
        "attn": {"op_norm": (D,), "wq": (D, H * d), "wk": (D, KV * d),
                 "wv": (D, KV * d), "q_norm": (d,), "k_norm": (d,),
                 "wo": (H * d, D)},
        "dense": {"ffn_norm": (D,), "w1": (D, I), "w3": (D, I),
                  "w2": (I, D)},
        "moe": {"ffn_norm": (D,), "router": (D, E), "router_bias": (E,),
                "e_gate": (E, D, Im), "e_up": (E, D, Im),
                "e_down": (E, Im, D)},
    }


def stack_sizes(cfg: Lfm2MoeConfig) -> Dict[str, int]:
    return {"conv": cfg.n_conv_layers, "attn": cfg.n_attn_layers,
            "dense": cfg.n_dense_layers, "moe": cfg.n_moe_layers}


# leaves kept in float32 whatever the compute dtype: the router's scores
# decide WHICH experts run (`router_bias` is the published
# `expert_bias`, under the name `dropless_moe` reads)
F32_LEAVES = ("router", "router_bias")
EXPERT_LEAVES = ("e_gate", "e_up", "e_down")


def init_params(cfg: Lfm2MoeConfig, key: jax.Array, std: float = 0.02):
    """Random weights in the tree the functions below read: `tok_emb`
    (also the head), `embedding_norm`, and the four stacks `conv`,
    `attn`, `dense`, `moe`, each leaf `[its layers, ...]`."""
    sizes = stack_sizes(cfg)
    out = {"tok_emb": (jax.random.normal(jax.random.fold_in(key, 99),
                                         (cfg.vocab_size, cfg.dim))
                       * std).astype(cfg.dtype),
           "embedding_norm": jnp.ones((cfg.dim,), cfg.dtype)}
    for s, (stack, shapes) in enumerate(sorted(layer_shapes(cfg).items())):
        n, leaves = sizes[stack], {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            dt = F32 if name in F32_LEAVES else cfg.dtype
            if name.endswith("norm"):
                leaves[name] = jnp.ones((n,) + shape, dt)
            else:
                k = jax.random.fold_in(jax.random.fold_in(key, s), i)
                leaves[name] = (jax.random.normal(k, (n,) + shape, F32)
                                * std).astype(dt)
        out[stack] = leaves
    return out


def init_cache(cfg: Lfm2MoeConfig, slots: int, max_len: int):
    """A dense cache for `slots` rows (`decode_step` without tables):
    `(k, v [attn_layers, slots, max_len, KV, hd], conv [conv_layers,
    slots, conv_L * D])`, zeroed."""
    kv = (cfg.n_attn_layers, slots, max_len, cfg.n_kv_heads, cfg.head_dim)
    return (jnp.zeros(kv, cfg.dtype), jnp.zeros(kv, cfg.dtype),
            jnp.zeros((cfg.n_conv_layers, slots, cfg.conv_L * cfg.dim),
                      cfg.dtype))


def layer_runs(cfg: Lfm2MoeConfig) -> List[tuple]:
    """The layers as runs of consecutive layers of one (operator, ffn)
    pair: `(op, ffn, first index into the operator's stack, first index
    into the ffn's stack, layers)`."""
    runs: List[list] = []
    seen = {"conv": 0, "attn": 0, "dense": 0, "moe": 0}
    for l, kind in enumerate(cfg.layer_types):
        if kind not in (CONV, ATTN):
            raise ValueError(f"layer_types[{l}] = {kind!r}")
        op = "conv" if kind == CONV else "attn"
        ffn = "dense" if l < cfg.n_dense_layers else "moe"
        if runs and runs[-1][:2] == [op, ffn]:
            runs[-1][4] += 1
        else:
            runs.append([op, ffn, seen[op], seen[ffn], 1])
        seen[op] += 1
        seen[ffn] += 1
    return [tuple(r) for r in runs]


# ----------------------------------------------------------------------
# parts
# ----------------------------------------------------------------------
def _at(stack: Dict, i) -> Dict:
    """Layer `i` (traced) of a stack's leaves, the expert leaves left
    out: a dynamic slice that fuses into the product that reads it."""
    return {k: lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
            for k, v in stack.items() if k not in EXPERT_LEAVES}


def _taps(cfg, w, window):
    """The convolution at one token: `window` [..., conv_L * D]
    tap-major, `w` [D, conv_L] -> [..., D]; summed in float32."""
    D = cfg.dim
    z = sum(w[:, k].astype(F32) * window[..., k * D:(k + 1) * D].astype(F32)
            for k in range(cfg.conv_L))
    return z.astype(cfg.dtype)


def _conv_in(cfg, layer, h):
    """h [..., D] normed -> (`B * u`, `C`), each [..., D]."""
    b, c, u = jnp.split(_apply(h, layer["w_in"], cfg.dtype), 3, axis=-1)
    return b * u, c


def _qkv(cfg, layer, h, rope):
    """h [..., D] normed -> (q [..., H, hd], k, v [..., KV, hd]), q and
    k normed a head and rotated by `rope`."""
    H, KV, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lead = h.shape[:-1]
    q = _apply(h, layer["wq"], cfg.dtype).reshape(lead + (H, d))
    k = _apply(h, layer["wk"], cfg.dtype).reshape(lead + (KV, d))
    v = _apply(h, layer["wv"], cfg.dtype).reshape(lead + (KV, d))
    q = rope(_rms_norm(q, layer["q_norm"].astype(cfg.dtype), cfg.norm_eps))
    k = rope(_rms_norm(k, layer["k_norm"].astype(cfg.dtype), cfg.norm_eps))
    return q, k, v


def _attend(cfg, q, k, v, mask):
    """Grouped-query attention without repeating K and V: q [B, T, H,
    hd], k / v [B, S, KV, hd], `mask` broadcastable to [B, KV, G, T, S]
    -> [B, T, H * hd]; scores and softmax in float32."""
    B, T, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, T, KV, H // KV, d)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                   preferred_element_type=F32) * d ** -0.5
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1).astype(cfg.dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v, preferred_element_type=F32)
    return o.astype(cfg.dtype).reshape(B, T, H * d)


def _ffn(cfg, params, kind, fi, x, *, kernel, interpret, row_mask):
    """A layer's second half, pre-norm and residual: x [..., D] ->
    (y, the routed layer's stats or None)."""
    layer = _at(params[kind], fi)
    h = _rms_norm(x, layer["ffn_norm"].astype(cfg.dtype), cfg.norm_eps)
    if kind == "dense":
        with jax.named_scope("dense_mlp"):
            act = (jax.nn.silu(_apply(h, layer["w1"], cfg.dtype))
                   * _apply(h, layer["w3"], cfg.dtype))
            return x + _apply(act, layer["w2"], cfg.dtype), None
    experts = {k: params["moe"][k] for k in EXPERT_LEAVES}
    y, stats = dropless_moe(
        h.reshape(-1, h.shape[-1]), {**layer, **experts}, top_k=cfg.top_k,
        scale=cfg.routed_scale, route_eps=cfg.route_eps, dtype=cfg.dtype,
        kernel=kernel, interpret=interpret, stack_index=fi,
        row_mask=row_mask)
    return x + y.reshape(h.shape), stats


def _head(cfg, params, x):
    """The tied head: x [..., D] -> float32 logits [..., vocab]."""
    x = _rms_norm(x, params["embedding_norm"].astype(cfg.dtype), cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("...d,vd->...v", x,
                          params["tok_emb"].astype(cfg.dtype),
                          preferred_element_type=F32)


def _run_layers(cfg, params, carry, layer):
    """Every run of `layer_runs` as one scan: `layer(op, ffn, oi, fi,
    carry) -> (carry, stats or None)`.  Returns the carry and the
    expert layers' stats summed (`experts_touched`) and maxed
    (`load_max`) over the layers."""
    touched = jnp.zeros((), jnp.int32)
    load_max = jnp.zeros((), jnp.int32)
    for op, ffn, o0, f0, n in layer_runs(cfg):
        def body(carry, i, op=op, ffn=ffn, o0=o0, f0=f0):
            return layer(op, ffn, o0 + i, f0 + i, carry)

        carry, stats = lax.scan(body, carry, jnp.arange(n, dtype=jnp.int32))
        if stats is not None:
            touched = touched + jnp.sum(stats["experts_touched"])
            load_max = jnp.maximum(load_max, jnp.max(stats["load_max"]))
    return carry, {"experts_touched": touched, "load_max": load_max}


# ----------------------------------------------------------------------
# prefill: one packed row
# ----------------------------------------------------------------------
def forward(cfg: Lfm2MoeConfig, params: Dict, tokens: jax.Array,
            conv=None, *, packed: Optional[Packed] = None, slots=None,
            kernel: bool = False, interpret: bool = False):
    """tokens [B, T] -> (logits float32, (ks, vs), conv).

    `packed` None: every row one prompt from position 0 (right-padding
    changes no real token's result), logits `[B, T, vocab]`.  `packed`
    (B == 1; `llama.Packed` with `seg` and `pos`): the row holds several
    prompts end to end; a token attends, and a tap reaches, inside its
    own prompt only, a padding token (`seg` < 0) is routed to no
    expert, and the logits are `[1, K, vocab]`, the rows `packed.last`.
    `ks`, `vs` `[attn_layers, B, T, KV, hd]`: the rows to cache.
    `conv` `[conv_layers, slots, conv_L * D]` with `slots` [K] (packed
    only): each prompt's convolution state at its last token is written
    into its slot, every convolution layer's; a slot out of range is
    dropped.  `conv` None: no state is kept."""
    B, T = tokens.shape
    D, L = cfg.dim, cfg.conv_L
    segmented = packed is not None and packed.seg is not None
    if segmented:
        if B != 1:
            raise ValueError("a packed prefill takes one row")
        pos, mask, real = packed.pos, packed.mask(), packed.seg >= 0
    else:
        pos, real = jnp.arange(T, dtype=jnp.int32), None
        mask = pos[:, None] >= pos[None, :]
    back = (L - 1) - jnp.arange(L)          # tap k reaches `back[k]` back
    if conv is not None:
        if packed is None or B != 1:
            raise ValueError("the convolution state is kept for a "
                             "packed row's prompts")
        # each prompt's window at its last token: [K, conv_L] rows of
        # the packed row, and which of them lie inside the prompt
        win = packed.last[:, None] - back[None, :]
        inside = (pos[packed.last][:, None] >= back[None, :])[..., None]
        win = jnp.maximum(win, 0)
    x = _embed(params, tokens, cfg.dtype).astype(cfg.dtype)
    kv_shape = (cfg.n_attn_layers, B, T, cfg.n_kv_heads, cfg.head_dim)

    def layer(op, ffn, oi, fi, carry):
        x, ks, vs, conv = carry
        w = _at(params[op], oi)
        h = _rms_norm(x, w["op_norm"].astype(cfg.dtype), cfg.norm_eps)
        if op == "conv":
            with jax.named_scope("short_conv"):
                bu, c = _conv_in(cfg, w, h)                 # [B, T, D]
                window = _windows(cfg, bu, pos)
                x = x + _apply(c * _taps(cfg, w["conv_w"], window),
                               w["w_out"], cfg.dtype)
                if conv is not None:
                    state = jnp.where(inside, bu[0][win],
                                      jnp.zeros((), cfg.dtype))
                    conv = conv.at[oi, slots].set(
                        state.reshape(-1, L * D).astype(conv.dtype),
                        mode="drop")
        else:
            with jax.named_scope("gqa_attn"):
                q, k, v = _qkv(cfg, w, h,
                               lambda t: _rope(t, cfg.rope_theta, pos=pos))
                x = x + _apply(_attend(cfg, q, k, v, mask), w["wo"],
                               cfg.dtype)
                ks = lax.dynamic_update_index_in_dim(ks, k, oi, 0)
                vs = lax.dynamic_update_index_in_dim(vs, v, oi, 0)
        x, stats = _ffn(cfg, params, ffn, fi, x, kernel=kernel,
                        interpret=interpret, row_mask=real)
        return (x, ks, vs, conv), stats

    carry = (x, jnp.zeros(kv_shape, cfg.dtype), jnp.zeros(kv_shape, cfg.dtype),
             conv)
    (x, ks, vs, conv), _ = _run_layers(cfg, params, carry, layer)
    if packed is not None:
        x = x[:, packed.last]  # the head reads K rows, not T
    return _head(cfg, params, x), (ks, vs), conv


def _windows(cfg, bu, pos):
    """Every token's convolution window: bu [B, T, D], pos [T] (the
    position inside its own prompt) -> [B, T, conv_L * D] tap-major,
    zeros where a tap would reach before the prompt's first token."""
    T, L = bu.shape[1], cfg.conv_L
    cols = []
    for k in range(L):
        j = L - 1 - k                       # tokens back
        shifted = jnp.pad(bu, ((0, 0), (j, 0), (0, 0)))[:, :T]
        cols.append(jnp.where((pos >= j)[None, :, None], shifted,
                              jnp.zeros((), bu.dtype)))
    return jnp.concatenate(cols, axis=-1)


# ----------------------------------------------------------------------
# decode: one step through both caches
# ----------------------------------------------------------------------
def decode_step(cfg: Lfm2MoeConfig, params: Dict, token: jax.Array, cache,
                pos, *, tables=None, live=None, kernel: bool = False,
                interpret: bool = False):
    """One decode step at per-row positions: token [B], pos [B], `cache`
    = `(k, v, conv)`.  `tables` [B, W] given: `k`, `v` are the paged
    pools `[attn_layers, NB, BS, *kv_pool_tail]`, appended to and read
    in place through the Pallas kernels (`ops/paged_attention.py`, the
    layer's index into the pools riding as a scalar).  `tables` None:
    the dense view `[attn_layers, B, M, KV, hd]` (or with the pool's
    folded tail: the same bytes), written by a masked select.  `conv`
    `[conv_layers, B, conv_L * D]`: row b's state in slot b, rolled one
    token.  Returns (logits [B, vocab] float32, cache, stats) with
    `stats` = `experts_touched`, `load_max` as
    `deepseek_v3.decode_step` counts them.

    `live` [B] bool (the engine's `pos < stop`; None: every row): a row
    that is not live appends nothing, attends nothing on the paged
    route, leaves its convolution state as it was and is routed to no
    expert."""
    B, D = token.shape[0], cfg.dim
    k_cache = cache[0]
    if tables is None:
        # the view's rows as heads, whatever tail the pool folds them in
        heads = k_cache.shape[:3] + (cfg.n_kv_heads, cfg.head_dim)
        cache = (cache[0].reshape(heads), cache[1].reshape(heads), cache[2])
        M = k_cache.shape[2]
        valid = (jnp.arange(M)[None, :] <= pos[:, None])[:, None, None, None]
        write = jnp.arange(M)[None, :] == pos[:, None]
        if live is not None:
            write = write & live[:, None]
        write = write[:, :, None, None]
    else:  # where a row appends, how far it attends
        w_pos, a_pos = _pa.dead_row_positions(pos, live, tables,
                                              k_cache.shape[2])
    x = _embed(params, token, cfg.dtype).astype(cfg.dtype)      # [B, D]

    def layer(op, ffn, oi, fi, carry):
        x, kc, vc, conv = carry
        w = _at(params[op], oi)
        h = _rms_norm(x, w["op_norm"].astype(cfg.dtype), cfg.norm_eps)
        if op == "conv":
            with jax.named_scope("short_conv"):
                bu, c = _conv_in(cfg, w, h)                      # [B, D]
                old = lax.dynamic_index_in_dim(conv, oi, 0, keepdims=False)
                new = jnp.concatenate([old[:, D:], bu.astype(conv.dtype)],
                                      axis=-1)
                x = x + _apply(c * _taps(cfg, w["conv_w"], new), w["w_out"],
                               cfg.dtype)
                if live is not None:
                    new = jnp.where(live[:, None], new, old)
                conv = lax.dynamic_update_index_in_dim(conv, new, oi, 0)
        else:
            with jax.named_scope("gqa_attn"):
                q, k, v = _qkv(
                    cfg, w, h[:, None],
                    lambda t: _rope_at(t, cfg.rope_theta, pos))
                if tables is not None:
                    kc, vc = _pa.paged_kv_append(
                        kc, vc, k[:, 0].astype(kc.dtype),
                        v[:, 0].astype(vc.dtype), tables, w_pos, oi,
                        interpret=interpret)
                    o = _pa.paged_decode_attention(
                        q[:, 0], kc, vc, tables, a_pos, oi,
                        interpret=interpret).reshape(B, -1)
                else:
                    rows = [jnp.where(write, new.astype(c.dtype),
                                      lax.dynamic_index_in_dim(
                                          c, oi, 0, keepdims=False))
                            for c, new in ((kc, k), (vc, v))]
                    kc, vc = (lax.dynamic_update_index_in_dim(c, r, oi, 0)
                              for c, r in zip((kc, vc), rows))
                    o = _attend(cfg, q, *rows, valid)[:, 0]
                x = x + _apply(o.astype(cfg.dtype), w["wo"], cfg.dtype)
        x, stats = _ffn(cfg, params, ffn, fi, x, kernel=kernel,
                        interpret=interpret, row_mask=live)
        return (x, kc, vc, conv), stats

    (x, kc, vc, conv), stats = _run_layers(cfg, params, (x, *cache), layer)
    cache = (kc.reshape(k_cache.shape), vc.reshape(k_cache.shape), conv)
    return _head(cfg, params, x), cache, stats
