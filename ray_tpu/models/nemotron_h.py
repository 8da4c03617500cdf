"""Nemotron-H-lineage decoder for SERVING: Mamba-2 layers whose context
is a recurrent state, a few attention layers, and a share of a wide
expert layer that works in a latent width.

The language model of `model_type: nemotron_h` checkpoints
(`nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16`; the published
`config.json`).  Every layer is ONE mixer behind a pre-norm, `x = x +
mixer(RMSNorm(x))`, of the kind `hybrid_override_pattern` names, then a
final RMSNorm and an untied head with float32 logits:

- `M`, Mamba-2: `in_proj` to a gate `z` `[d_inner]`, `xBC` `[d_inner +
  2 G N]` and `dt` `[heads]`; a causal depthwise convolution of
  `conv_kernel` taps with bias over `xBC`, then SiLU; `xBC` split into
  `x` `[heads, head_dim]`, `B` and `C` `[G, N]` (the heads of a group
  share them); `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; the
  recurrence `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t = S_t
  C_t + D x_t` (`ops/ssd.py`); the gated norm `RMSNorm(y * silu(z))` in
  `G` groups with a gain; `out_proj`;
- `*`, attention: grouped queries from one fused projection, causal
  softmax at `1 / sqrt(head_dim)`, NO rotation (position comes from the
  Mamba layers), `o_proj`;
- `E`, experts in a LATENT width: the router `sigmoid(h W_g)` over all
  `n_routed_experts` in float32, the top-k of `scores + bias`, weights
  from the scores normalised and scaled (`sigmoid_topk_route`); `u = h
  W_in` (`dim -> latent`); expert `e` is `W2_e relu(W1_e u)^2`, two
  matrices, no gate; `routed = (sum_e w_e y_e) W_out` (`latent ->
  dim`); beside it one shared expert `W2_s relu(W1_s h)^2` on the
  `dim`-wide input.  This chip HOLDS `experts_held` of the experts from
  `expert_offset` on (`parallel/moe.dropless_moe(held=)`): a pair routed
  elsewhere adds nothing here, and `routed` is this chip's partial sum.

THE CACHE is of both kinds over DISJOINT layers
(`serve/engine_model.RecurrentEngineModel`): paged `k` / `v` `[attention
layers, NB, BS, KV * head_dim]` (a token's heads folded side by side),
per-slot `ssm` `[Mamba layers, slots, heads, head_dim, N]` float32 and
`conv` `[Mamba layers, slots, (taps - 1) * conv_dim]` in the model's
dtype, and nothing in the expert layers.  A slot's bytes do not grow
with its context.

Three programs: `decode_step` (a row a sequence: the state stepped, the
attention layers through the block table), `forward` (a packed row of
whole prompts from position 0: the scan and the convolution RESET at a
segment's start, each prompt's end state left in its slot) and
`forward_chunk` (the next `N` tokens of ONE sequence: a Mamba layer
starts from the SLOT's state, zero for a prompt's first chunk, and
leaves it as it stands at the chunk's end; an attention layer writes
the chunk's rows into the sequence's blocks and attends the earlier
chunks through the table).

`jax.named_scope`s, named after what the model does and not after what
computes it: `ssm_proj` (in_proj, gated norm, out_proj), `ssm_conv`,
`ssm_scan` (admission), `ssm_step` (decode), `full_attn`, `moe_router`,
`latent_moe_proj` (`W_in`, `W_out`), `latent_moe_routed` (the held
experts' grouped products and the way back), `moe_shared`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import Packed, _apply, _embed, _lm_head, _rms_norm
from ray_tpu.models.mimo_v2 import KEY_BLOCK, _attend
from ray_tpu.ops import paged_attention as _pa
from ray_tpu.ops import ssd
from ray_tpu.ops.prefill_attention import prefill_attention
from ray_tpu.parallel.moe import dropless_moe, sigmoid_topk_route

F32 = jnp.float32
MAMBA, ATTN, MOE = "M", "*", "E"
ROUTE_EPS = 1e-20
# the fused prefill attention's (query rows, keys) a step: MiMo's sweep
# at the same 2,048-row chunks (`models/mimo_v2.FUSED_BLOCKS`)
FUSED_BLOCKS = (128, 1024)
F32_LEAVES = ("router", "router_bias", "dt_bias", "A_log", "D")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    max_seq_len: int = 262144
    dim: int = 4096
    pattern: str = "MEMEMEM*EME"      # one mixer a layer
    # Mamba-2
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    scan_chunk: int = 128
    # attention
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # experts
    latent: int = 1024
    moe_intermediate: int = 2688
    shared_intermediate: int = 5376
    n_routed_experts: int = 512       # the router's width
    experts_held: int = 512           # this chip's share of them ...
    expert_offset: int = 0            # ... from this expert on
    top_k: int = 22
    routed_scale: float = 5.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "dense"          # what the engine's packed prefill asks

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def n_mamba_layers(self) -> int:
        return self.pattern.count(MAMBA)

    @property
    def n_attn_layers(self) -> int:
        return self.pattern.count(ATTN)

    @property
    def n_moe_layers(self) -> int:
        return self.pattern.count(MOE)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    @staticmethod
    def tiny(vocab_size: int = 256) -> "NemotronHConfig":
        return NemotronHConfig(
            vocab_size=vocab_size, max_seq_len=512, dim=64,
            pattern="MEM*EME", mamba_heads=8, mamba_head_dim=8, n_groups=2,
            state_size=16, scan_chunk=8, n_heads=4, n_kv_heads=2,
            head_dim=16, latent=32, moe_intermediate=48,
            shared_intermediate=96, n_routed_experts=16, experts_held=4,
            expert_offset=4, top_k=6, dtype=jnp.float32)


def layer_shapes(cfg: NemotronHConfig, i: int) -> Dict[str, tuple]:
    """Layer `i`'s leaves, by its kind."""
    D, kind = cfg.dim, cfg.pattern[i]
    out = {"norm": (D,)}
    if kind == MAMBA:
        di, H = cfg.d_inner, cfg.mamba_heads
        out.update({"in_proj": (D, di + cfg.conv_dim + H),
                    "conv_w": (cfg.conv_kernel, cfg.conv_dim),
                    "conv_b": (cfg.conv_dim,), "dt_bias": (H,),
                    "A_log": (H,), "D": (H,), "gate_norm": (di,),
                    "out_proj": (di, D)})
    elif kind == ATTN:
        hd = cfg.head_dim
        out.update({"wqkv": (D, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd),
                    "wo": (cfg.n_heads * hd, D)})
    else:
        E, Eh, Z = cfg.n_routed_experts, cfg.experts_held, cfg.latent
        Im, Is = cfg.moe_intermediate, cfg.shared_intermediate
        out.update({"router": (D, E), "router_bias": (E,), "w_in": (D, Z),
                    "e_up": (Eh, Z, Im), "e_down": (Eh, Im, Z),
                    "w_out": (Z, D), "s_up": (D, Is), "s_down": (Is, D)})
    return out


def init_params(cfg: NemotronHConfig, key: jax.Array, std: float = 0.02):
    """Random weights in the tree the functions below read: `tok_emb`,
    `final_norm`, `lm_head`, and `layers`, one dict a layer.  `dt_bias`
    is the inverse softplus of a log-uniform step in [1e-3, 1e-1], `A_log
    = log U[1, 16]`, `D = 1` (Mamba-2's usual initialisation): decays
    that hold a state over tens to hundreds of tokens."""
    layers = []
    for i in range(cfg.n_layers):
        lk, leaves = jax.random.fold_in(key, i), {}
        for j, (name, shape) in enumerate(sorted(layer_shapes(cfg, i).items())):
            k = jax.random.fold_in(lk, j)
            dt = F32 if name in F32_LEAVES else cfg.dtype
            if name.endswith("norm") or name == "D":
                leaves[name] = jnp.ones(shape, dt)
            elif name == "dt_bias":
                step = jnp.exp(jax.random.uniform(k, shape, F32)
                               * (math.log(0.1) - math.log(1e-3))
                               + math.log(1e-3))
                leaves[name] = step + jnp.log(-jnp.expm1(-step))
            elif name == "A_log":
                leaves[name] = jnp.log(
                    jax.random.uniform(k, shape, F32, 1.0, 16.0))
            elif name in ("conv_w", "conv_b"):  # a depthwise Conv1d's
                bound = cfg.conv_kernel ** -0.5
                leaves[name] = jax.random.uniform(
                    k, shape, F32, -bound, bound).astype(dt)
            else:
                leaves[name] = (jax.random.normal(k, shape, F32)
                                * std).astype(dt)
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


def leaf_index(cfg: NemotronHConfig, i: int) -> int:
    """Layer `i`'s place among the layers of its own kind: its row of
    the cache leaves that kind holds."""
    return cfg.pattern[:i].count(cfg.pattern[i])


# ----------------------------------------------------------------------
# parts
# ----------------------------------------------------------------------
def _relu2(h, up, down, dtype):
    return _apply(jnp.square(jax.nn.relu(_apply(h, up, dtype))), down, dtype)


def _mamba_in(cfg, layer, h):
    """h `[T, D]` normed -> (z `[T, d_inner]`, xBC `[T, conv_dim]`, dt
    `[T, heads]` float32 after its softplus)."""
    di, cd = cfg.d_inner, cfg.conv_dim
    with jax.named_scope("ssm_proj"):
        # held, not made again: XLA's rematerialization otherwise runs
        # this product up to four times in a chunk program, once for
        # each of `z`, `xBC` and `dt` where they are read
        zxd = lax.optimization_barrier(_apply(h, layer["in_proj"],
                                              cfg.dtype))
        dt = jax.nn.softplus(zxd[:, di + cd:].astype(F32)
                             + layer["dt_bias"].astype(F32)[None])
    return zxd[:, :di], zxd[:, di:di + cd], dt


def _split_xbc(cfg, xbc):
    """The convolution's output `[T, conv_dim]` -> (x `[T, H, P]`, B
    `[T, G, N]`, C `[T, G, N]`) in the model's dtype."""
    T, di, gn = xbc.shape[0], cfg.d_inner, cfg.n_groups * cfg.state_size
    xbc = xbc.astype(cfg.dtype)
    return (xbc[:, :di].reshape(T, cfg.mamba_heads, cfg.mamba_head_dim),
            xbc[:, di:di + gn].reshape(T, cfg.n_groups, cfg.state_size),
            xbc[:, di + gn:].reshape(T, cfg.n_groups, cfg.state_size))


def _mamba_out(cfg, layer, y, x, z):
    """y `[T, H, P]` float32 (the recurrence's), x `[T, H, P]`, z `[T,
    d_inner]` -> the mixer's output `[T, D]`: the skip `D x`, the gated
    norm in `n_groups` groups (float32 statistics), `out_proj`."""
    T, G = y.shape[0], cfg.n_groups
    with jax.named_scope("ssm_proj"):
        y = y + layer["D"].astype(F32)[None, :, None] * x.astype(F32)
        g = (y.reshape(T, -1) * jax.nn.silu(z.astype(F32))).reshape(T, G, -1)
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + cfg.norm_eps)
        g = g.reshape(T, -1).astype(cfg.dtype) * layer["gate_norm"].astype(
            cfg.dtype)
        return _apply(g, layer["out_proj"], cfg.dtype)


def _qkv(cfg, layer, h):
    """h `[T, D]` normed -> (q `[T, KV, G, hd]`, k `[T, KV, hd]`, v `[T,
    KV, hd]`); nothing is rotated."""
    T, hd, H, KV = h.shape[0], cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    qkv = _apply(h, layer["wqkv"], cfg.dtype)
    return (qkv[:, :H * hd].reshape(T, KV, H // KV, hd),
            qkv[:, H * hd:(H + KV) * hd].reshape(T, KV, hd),
            qkv[:, (H + KV) * hd:].reshape(T, KV, hd))


def _prefill_attend(cfg, q, k, v, qseg, kseg, lo, *, paged_kernel, interpret):
    """q `[N, KV, G, hd]` whose row `i` is key row `lo + i` of the
    contiguous k / v `[S, KV, hd]`, seen where the segments agree and
    the key is not past the query -> `[N, KV, G, hd]`: one fused kernel
    (`paged_kernel`: `ops/prefill_attention.py`) or one masked softmax a
    block of `KEY_BLOCK` query rows in plain XLA (`mimo_v2._attend` with
    no sink; a padding row's result is nobody's)."""
    if paged_kernel:
        return prefill_attention(
            q, k, v, qseg, kseg, lo, scale=cfg.head_dim ** -0.5,
            block_q=FUSED_BLOCKS[0], block_k=FUSED_BLOCKS[1],
            interpret=interpret)
    N, S = q.shape[0], k.shape[0]
    QB = max(d for d in range(1, min(KEY_BLOCK, N) + 1) if N % d == 0)
    kpos = jnp.arange(S, dtype=jnp.int32)

    def block(t0):
        cut = lambda t: lax.dynamic_slice_in_dim(t, t0, QB, 0)  # noqa: E731
        qpos = lo + t0 + jnp.arange(QB, dtype=jnp.int32)
        qs = cut(qseg)
        mask = ((qs[:, None] == kseg[None, :]) & (qs[:, None] >= 0)
                & (kpos[None, :] <= qpos[:, None]))
        return _attend(cfg, cut(q), k, v, mask, None)

    o = lax.map(block, jnp.arange(0, N, QB, dtype=jnp.int32))
    return o.reshape((N,) + o.shape[2:])


def _attn_out(cfg, layer, o):
    return _apply(o.astype(cfg.dtype).reshape(o.shape[0], -1), layer["wo"],
                  cfg.dtype)


def _latent_moe(cfg, layer, h, *, kernel, interpret, row_mask):
    """h `[T, D]` normed -> (this chip's part of the routed sum through
    `W_out` plus the shared expert, `dropless_moe`'s stats)."""
    dt = cfg.dtype
    with jax.named_scope("moe_router"):
        picks = sigmoid_topk_route(h, layer["router"], layer["router_bias"],
                                   cfg.top_k, cfg.routed_scale, ROUTE_EPS)
    with jax.named_scope("latent_moe_proj"):
        u = _apply(h, layer["w_in"], dt)
    with jax.named_scope("latent_moe_routed"):
        y, stats = dropless_moe(
            u, layer, top_k=cfg.top_k, scale=cfg.routed_scale,
            route_eps=ROUTE_EPS, dtype=dt, kernel=kernel,
            interpret=interpret, row_mask=row_mask,
            held=(cfg.expert_offset, cfg.experts_held),
            route=lambda *_: picks)
    with jax.named_scope("latent_moe_proj"):
        y = _apply(y, layer["w_out"], dt)
    with jax.named_scope("moe_shared"):
        y = y + _relu2(h, layer["s_up"], layer["s_down"], dt)
    return y, stats


def _head(cfg, params, x):
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    return _lm_head(x, params, cfg.dtype)


def _norm(cfg, layer, x):
    return _rms_norm(x, layer["norm"].astype(cfg.dtype), cfg.norm_eps)


def _mamba_scan(cfg, layer, h, seg, ends, state, *, kernel, interpret):
    """A Mamba layer over a row: h `[T, D]` normed, `state` = `(ssm [H,
    P, N], conv [taps - 1, conv_dim])` sequence 0 continues from, or
    None -> (the mixer's output `[T, D]`, the states after the tokens
    `ends` names: ssm `[K, H, P, N]` float32, conv `[K, (taps - 1) *
    conv_dim]`).  `kernel`: the scan's chunks as one Pallas call
    (`ops/ssd.py`)."""
    z, xbc, dt = _mamba_in(cfg, layer, h)
    with jax.named_scope("ssm_conv"):
        xbc, conv = ssd.conv_scan(
            xbc, layer["conv_w"], layer["conv_b"], seg, ends,
            prev=None if state is None else state[1])
    x, B, C = _split_xbc(cfg, xbc)
    with jax.named_scope("ssm_scan"):
        y, ssm = ssd.ssd_scan(
            x, dt, -jnp.exp(layer["A_log"].astype(F32)), B, C, seg, ends,
            init=None if state is None else state[0], dtype=cfg.dtype,
            chunk=cfg.scan_chunk, kernel=kernel, interpret=interpret)
    return (_mamba_out(cfg, layer, y, x, z),
            ssm, conv.reshape(conv.shape[0], -1))


# ----------------------------------------------------------------------
# prefill: a packed row of whole prompts
# ----------------------------------------------------------------------
def forward(cfg: NemotronHConfig, params: Dict, tokens: jax.Array,
            state=None, *, packed: Optional[Packed] = None, slots=None,
            kernel: bool = False, interpret: bool = False,
            paged_kernel: bool = False):
    """tokens [1, T] -> (logits float32, (ks, vs), state).

    `packed` None: one prompt from position 0, right-padded: logits `[1,
    T, vocab]`.  `packed` (`llama.Packed`): up to `K` prompts end to
    end, a token sees its own prompt only (the scan and the convolution
    RESET at a segment's start), logits `[1, K, vocab]` of the rows
    `packed.last` names.  `ks` / `vs` `[attention layers, 1, T, KV *
    hd]`: the rows the attention layers cache, as the pools hold them.
    `state` = `(ssm, conv)` `[Mamba layers, slots, ..]` with `slots` [K]
    (past the last: dropped): each prompt's END state goes into its
    slot; None: none is kept."""
    B, T = tokens.shape
    if B != 1:
        raise ValueError("a prefill takes one row")
    dt = cfg.dtype
    if packed is not None and packed.seg is not None:
        seg = packed.seg
    else:
        seg = jnp.zeros((T,), jnp.int32)
    real = seg >= 0
    ends = (packed.last if packed is not None
            else jnp.full((1,), T - 1, jnp.int32))
    x = _embed(params, tokens[0], dt).astype(dt)
    ks, vs = [], []
    for i, layer in enumerate(params["layers"]):
        kind, li = cfg.pattern[i], leaf_index(cfg, i)
        h = _norm(cfg, layer, x)
        if kind == MAMBA:
            y, ssm, conv = _mamba_scan(cfg, layer, h, seg, ends, None,
                                       kernel=kernel, interpret=interpret)
            if state is not None:
                state = (state[0].at[li, slots].set(ssm, mode="drop"),
                         state[1].at[li, slots].set(
                             conv.astype(state[1].dtype), mode="drop"))
        elif kind == ATTN:
            with jax.named_scope("full_attn"):
                q, k, v = _qkv(cfg, layer, h)
                y = _attn_out(cfg, layer, _prefill_attend(
                    cfg, q, k, v, seg, seg, 0, paged_kernel=paged_kernel,
                    interpret=interpret))
                ks.append(k.reshape(T, -1))
                vs.append(v.reshape(T, -1))
        else:
            y, _ = _latent_moe(cfg, layer, h, kernel=kernel,
                               interpret=interpret, row_mask=real)
        x = x + y
    if packed is not None:
        x = x[packed.last]
    none = jnp.zeros((0, 1, T, cfg.n_kv_heads * cfg.head_dim), dt)
    kv = tuple(jnp.stack(t)[:, None] if t else none for t in (ks, vs))
    return _head(cfg, params, x)[None], kv, state


# ----------------------------------------------------------------------
# prefill: a chunk of ONE long prompt, from the slot's state
# ----------------------------------------------------------------------
def forward_chunk(cfg: NemotronHConfig, params: Dict, tokens: jax.Array,
                  lo, n, cache, table: jax.Array, slot, *,
                  kernel: bool = False, interpret: bool = False,
                  paged_kernel: bool = False):
    """Tokens `lo .. lo + n` of one sequence, `tokens` [N] (the first
    `n` real), behind the `lo` tokens that earlier chunks took in:
    `cache` = `(k, v, ssm, conv)`, `table` [W] the sequence's blocks,
    `slot` its slot (past the last: a warm-up, written nowhere); `lo`
    starts a cache block.  A Mamba layer starts from the slot's `ssm`
    and `conv` (ZERO where `lo` is 0: a prompt's first chunk, whatever
    the slot held) and leaves them as they stand after token `lo + n -
    1`; an attention layer writes the chunk's rows into the sequence's
    blocks, whole blocks, then attends positions `0 .. lo + n` through
    the table.  Returns (logits [vocab] float32 of the chunk's last
    real token, the cache)."""
    dt = cfg.dtype
    k_pool, v_pool, ssm, conv = cache
    NB, BS = k_pool.shape[1:3]
    N, W = tokens.shape[0], table.shape[0]
    if N % BS:
        raise ValueError(f"{N} rows are no whole cache blocks of {BS}")
    row = jnp.arange(N, dtype=jnp.int32)
    real = row < n
    seg = jnp.where(real, 0, -1)
    ends = jnp.maximum(n - 1, 0).reshape(1)
    resumes = lo > 0
    written = (slot >= 0) & (slot < ssm.shape[1])
    at = jnp.clip(slot, 0, ssm.shape[1] - 1).astype(jnp.int32)
    # the cache block each block of BS rows is written to; padding: none
    first = lo // BS + jnp.arange(N // BS)
    wblk = jnp.where(row[::BS] < n, table[jnp.clip(first, 0, W - 1)], NB)
    x = _embed(params, tokens, dt).astype(dt)
    for i, layer in enumerate(params["layers"]):
        kind, li = cfg.pattern[i], leaf_index(cfg, i)
        h = _norm(cfg, layer, x)
        if kind == MAMBA:
            was = (ssm[li, at], conv[li, at])
            held = (jnp.where(resumes, was[0], 0.0),
                    jnp.where(resumes, was[1], jnp.zeros((), conv.dtype)
                              ).reshape(cfg.conv_kernel - 1, -1))
            y, s_end, c_end = _mamba_scan(cfg, layer, h, seg, ends, held,
                                          kernel=kernel, interpret=interpret)
            # ONE slot's rows written in place (a scatter that may drop
            # its row lowers to a pass over the whole 2.7 GB leaf): a
            # warm-up's slot, past the last, writes back what was there
            ssm, conv = (
                lax.dynamic_update_slice(
                    leaf, jnp.where(written, new[0].astype(leaf.dtype),
                                    old)[None, None],
                    (jnp.int32(li), at) + (jnp.int32(0),) * (leaf.ndim - 2))
                for leaf, new, old in ((ssm, s_end, was[0]),
                                       (conv, c_end, was[1])))
        elif kind == ATTN:
            with jax.named_scope("full_attn"):
                q, k, v = _qkv(cfg, layer, h)
                k_pool, v_pool = (
                    pool.at[li, wblk].set(
                        t.reshape(N // BS, BS, -1).astype(pool.dtype),
                        mode="drop")
                    for pool, t in ((k_pool, k), (v_pool, v)))
                y = _attn_out(cfg, layer, _prefill_attend(
                    cfg, q,
                    k_pool[li, table].reshape(W * BS, cfg.n_kv_heads, -1)
                    .astype(dt),
                    v_pool[li, table].reshape(W * BS, cfg.n_kv_heads, -1)
                    .astype(dt),
                    seg, jnp.zeros((W * BS,), jnp.int32), lo,
                    paged_kernel=paged_kernel, interpret=interpret))
        else:
            y, _ = _latent_moe(cfg, layer, h, kernel=kernel,
                               interpret=interpret, row_mask=real)
        x = x + y
    return (_head(cfg, params, x[jnp.maximum(n - 1, 0)][None])[0],
            (k_pool, v_pool, ssm, conv))


# ----------------------------------------------------------------------
# decode: one step through the three leaves
# ----------------------------------------------------------------------
def decode_step(cfg: NemotronHConfig, params: Dict, token: jax.Array, cache,
                pos, tables, *, live=None, kernel: bool = False,
                interpret: bool = False, paged_kernel: bool = False):
    """One decode step at per-row positions: token [B], pos [B], `cache`
    = `(k, v, ssm, conv)`, `tables` [B, W] each row's blocks (row b sits
    in slot b).  A Mamba layer steps each row's state (float32) and
    rolls its convolution state; an attention layer appends its row to
    the paged pools and attends positions `0 .. pos` through the table
    (`paged_kernel`: the Pallas kernels of `ops/paged_attention.py` on
    the folded pools; else plain XLA).  Returns (logits [B, vocab]
    float32, cache, stats) with `stats` = `experts_touched`, `load_max`,
    `held_pairs` over the HELD experts.

    `live` [B] bool (the engine's `pos < stop`; None: every row): a row
    that is not live writes nothing, neither block nor state, and is
    routed to no expert."""
    dt = cfg.dtype
    k_pool, v_pool, ssm, conv = cache
    NB, BS = k_pool.shape[1:3]
    B, W = tables.shape
    H, KV = cfg.n_heads, cfg.n_kv_heads
    if paged_kernel:
        w_pos, a_pos = _pa.dead_row_positions(pos, live, tables, BS)
    else:
        blk = jnp.take_along_axis(
            tables, jnp.clip(pos // BS, 0, W - 1)[:, None], axis=1)[:, 0]
        if live is not None:
            blk = jnp.where(live, blk, NB)
        valid = jnp.arange(W * BS)[None, :] <= pos[:, None]
    x = _embed(params, token, dt).astype(dt)                       # [B, D]
    zero = jnp.zeros((), jnp.int32)
    touched, load_max, pairs = zero, zero, zero
    for i, layer in enumerate(params["layers"]):
        kind, li = cfg.pattern[i], leaf_index(cfg, i)
        h = _norm(cfg, layer, x)
        if kind == MAMBA:
            z, xbc, step = _mamba_in(cfg, layer, h)
            with jax.named_scope("ssm_conv"):
                xbc, c_new = ssd.conv_step(
                    conv[li].reshape(B, cfg.conv_kernel - 1, -1), xbc,
                    layer["conv_w"], layer["conv_b"], live)
                conv = conv.at[li].set(c_new.reshape(B, -1))
            xs, Bm, Cm = _split_xbc(cfg, xbc)
            with jax.named_scope("ssm_step"):
                y, s_new = ssd.ssd_step(
                    ssm[li], xs, step, -jnp.exp(layer["A_log"].astype(F32)),
                    Bm, Cm, live)
                ssm = ssm.at[li].set(s_new)
            y = _mamba_out(cfg, layer, y, xs, z)
        elif kind == ATTN:
            with jax.named_scope("full_attn"):
                q, k, v = _qkv(cfg, layer, h)
                k, v = k.reshape(B, -1), v.reshape(B, -1)
                if paged_kernel:
                    k_pool, v_pool = _pa.paged_kv_append(
                        k_pool, v_pool, k.astype(k_pool.dtype),
                        v.astype(v_pool.dtype), tables, w_pos, li,
                        interpret=interpret)
                    o = _pa.paged_decode_attention(
                        q.reshape(B, H, -1), k_pool, v_pool, tables, a_pos,
                        li, interpret=interpret)
                    o = o.reshape(B, KV, H // KV, -1)
                else:
                    k_pool, v_pool = (
                        pool.at[li, blk, pos % BS].set(
                            t.astype(pool.dtype), mode="drop")
                        for pool, t in ((k_pool, k), (v_pool, v)))
                    o = _attend(
                        cfg, q[:, None],
                        k_pool[li, tables].reshape(B, W * BS, KV, -1)
                        .astype(dt),
                        v_pool[li, tables].reshape(B, W * BS, KV, -1)
                        .astype(dt), valid[:, None, :], None)[:, 0]
                y = _attn_out(cfg, layer, o)
        else:
            y, stats = _latent_moe(cfg, layer, h, kernel=kernel,
                                   interpret=interpret, row_mask=live)
            touched = touched + stats["experts_touched"]
            load_max = jnp.maximum(load_max, stats["load_max"])
            pairs = pairs + stats["held_pairs"]
        x = x + y
    return (_head(cfg, params, x), (k_pool, v_pool, ssm, conv),
            {"experts_touched": touched, "load_max": load_max,
             "held_pairs": pairs})
