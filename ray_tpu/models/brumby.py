"""Brumby-lineage decoder for SERVING: every layer a POWER RETENTION
layer (a gated linear attention of degree 2) in place of softmax
attention, on the block it was retrained from (Qwen3's: pre-norm
RMSNorm, grouped query heads with per-head `q_norm` / `k_norm`, rotary
over the whole head, SwiGLU, untied head).

Manifest AI, "Scaling Context Requires Rethinking Attention"
(arXiv:2507.04239) and the `Brumby-14B-Base` release with its
`retention` package.  The published `config.json` is the Qwen3-14B
shape key for key and carries no key of the retention, so what it
does not state is ASSUMED here (and listed in the benchmark's
configuration file): degree `p = 2`; one log-gate a KV head, `g =
log sigmoid(h W_g + b_g)` from a `[dim, n_kv_heads]` projection with a
bias, in float32; the normaliser is the gated sum of `phi(k)`; `eps`
1e-6; `q_norm`, `k_norm` and rotary kept from the Qwen3 block; no
softmax and no 1/sqrt(d) (a scale of `q . k` cancels between numerator
and denominator); the state and the key sum float32.

A layer, `x` [T, dim]:

    h = RMSNorm(x);  q = h W_q -> [T, H, d];  k, v = h W_k, h W_v -> [T, KV, d]
    q, k = rotary(RMSNorm_d(q)), rotary(RMSNorm_d(k))     (halves, not pairs)
    g = log sigmoid(h W_g + b_g) -> [T, KV]               (float32)
    o = power_retention(q, k, v, g)                        (ops/retention.py)
    x += concat(o) W_o;  x += W_down(silu(h' W_gate) * (h' W_up)), h' = RMSNorm(x)

What the serve engine needs of a model, and nothing else:

- `forward`: prefill over a PACKED row (`llama.Packed`: several prompts
  end to end, each from a multiple of `chunk`), a chunked scan that
  leaves each prompt's state in its slot of the cache;
- `decode_step`: one token for every live row off its slot's state, the
  state updated in place; a dead row's state is untouched;
- `chunk_step`: the same as one step of a CHUNK whose state is written
  once: the steps before the last READ the state and hold their keys
  and values beside it, the last folds them in (`ops/retention.py`).

The cache is two leaves a model, per SLOT and not per token
(`retention.state_shapes`): nothing grows with the context.
`jax.named_scope`s `retention_attn` and `dense_mlp` mark the two halves
in a device trace; inside the first the kernels are `retention_prefill`,
`retention_read` and `retention_decode`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import (Packed, _apply, _embed, _lm_head, _mlp,
                                  _rms_norm, _rope, _rope_at)
from ray_tpu.ops import retention as _ret

F32 = jnp.float32
# leaves kept in float32 whatever the compute dtype: the gate's
# logarithm is summed over a whole context
F32_LEAVES = ("wg", "bg")


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    max_seq_len: int = 32768
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    intermediate: int = 17408
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    retention_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @staticmethod
    def tiny(vocab_size: int = 256) -> "BrumbyConfig":
        return BrumbyConfig(
            vocab_size=vocab_size, max_seq_len=128, dim=64, n_layers=3,
            n_heads=6, n_kv_heads=2, head_dim=16, intermediate=128,
            dtype=jnp.float32)


def layer_shapes(cfg: BrumbyConfig) -> Dict[str, tuple]:
    D, H, KV, d = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "attn_norm": (D,), "wq": (D, H * d), "wk": (D, KV * d),
        "wv": (D, KV * d), "q_norm": (d,), "k_norm": (d,),
        "wg": (D, KV), "bg": (KV,), "wo": (H * d, D), "mlp_norm": (D,),
        "w_gate": (D, cfg.intermediate), "w_up": (D, cfg.intermediate),
        "w_down": (cfg.intermediate, D),
    }


# the seeded gate bias: sigmoid 0.990 .. 0.999, time constants of 100 to
# 1,000 tokens.  At 0 random weights forget in two tokens and no check
# could see a broken state path
GATE_BIAS = (4.6, 6.9)


def init_params(cfg: BrumbyConfig, key: jax.Array, std: float = 0.02):
    """Random weights in the tree the functions below read: `tok_emb`,
    `final_norm`, `lm_head`, and `blocks`, each leaf `[layers, ...]`."""
    L = cfg.n_layers
    blocks = {}
    for i, (name, shape) in enumerate(sorted(layer_shapes(cfg).items())):
        dt = F32 if name in F32_LEAVES else cfg.dtype
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            blocks[name] = jnp.ones((L,) + shape, dt)
        elif name == "bg":
            blocks[name] = jax.random.uniform(
                k, (L,) + shape, F32, *GATE_BIAS)
        else:
            blocks[name] = (jax.random.normal(k, (L,) + shape, F32)
                            * std).astype(dt)
    ke, kh = jax.random.split(jax.random.fold_in(key, 1000))
    return {
        "tok_emb": (jax.random.normal(ke, (cfg.vocab_size, cfg.dim))
                    * std).astype(cfg.dtype),
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": (jax.random.normal(kh, (cfg.dim, cfg.vocab_size))
                    * std).astype(cfg.dtype),
        "blocks": blocks,
    }


def init_cache(cfg: BrumbyConfig, slots: int, layers: Optional[int] = None):
    """Zeroed (`state`, `keysum`) for `slots` sequences."""
    return tuple(jnp.zeros(s, F32) for s in _ret.state_shapes(
        cfg.n_layers if layers is None else layers, slots, cfg.n_kv_heads,
        cfg.head_dim))


def _qkvg(cfg, layer, x, rope):
    """The projections both forms share: x [B, T, D] -> (q [B, T, H, d],
    k, v [B, T, KV, d], g [B, T, KV] float32 log-gates)."""
    H, KV, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = _rms_norm(x, layer["attn_norm"].astype(cfg.dtype), cfg.norm_eps)
    lead = h.shape[:-1]
    q = _apply(h, layer["wq"], cfg.dtype).reshape(lead + (H, d))
    k = _apply(h, layer["wk"], cfg.dtype).reshape(lead + (KV, d))
    v = _apply(h, layer["wv"], cfg.dtype).reshape(lead + (KV, d))
    q = rope(_rms_norm(q, layer["q_norm"].astype(cfg.dtype), cfg.norm_eps))
    k = rope(_rms_norm(k, layer["k_norm"].astype(cfg.dtype), cfg.norm_eps))
    g = jax.nn.log_sigmoid(
        jnp.matmul(h.astype(F32), layer["wg"].astype(F32),
                   precision="highest") + layer["bg"].astype(F32))
    return q, k, v, g


def _out(cfg, layer, x, o):
    """The retention's result through `W_o`, then the dense half."""
    x = x + _apply(o.astype(cfg.dtype).reshape(x.shape[:-1] + (-1,)),
                   layer["wo"], cfg.dtype)
    with jax.named_scope("dense_mlp"):
        return _mlp(cfg, x, layer)


# ----------------------------------------------------------------------
# prefill: a chunked scan over a packed row
# ----------------------------------------------------------------------
def forward(cfg: BrumbyConfig, params: Dict, tokens: jax.Array,
            cache=None, *, packed: Optional[Packed] = None, slots=None,
            chunk: int = 128, kernel: bool = False, interpret: bool = False):
    """tokens [1, T] -> (logits float32, cache).

    `packed` None: one prompt from position 0, logits `[1, T, vocab]`.
    `packed` (`llama.Packed` with `seg` and `pos`): the row holds
    several prompts end to end, each from a multiple of `chunk`, its
    padding behind it; a token sees its own prompt only (a prompt's
    first chunk carries nothing in), and the logits are `[1, K,
    vocab]`, the rows `packed.last`.  `cache` = (`state`, `keysum`)
    with `slots` [K]: each prompt's state at its last real token is
    written into its slot, every layer's.  `cache` None: nothing is
    kept (one slot of scratch a layer)."""
    B, T = tokens.shape
    if B != 1:
        raise ValueError("the retention prefill takes one packed row")
    pad = -T % chunk
    if packed is None:
        seg = jnp.concatenate([jnp.zeros((T,), jnp.int32),
                               jnp.full((pad,), -1, jnp.int32)])
        posn = jnp.arange(T + pad, dtype=jnp.int32)
    else:
        if pad:
            raise ValueError(f"a packed row of {T} tokens is not whole "
                             f"chunks of {chunk}")
        seg, posn = packed.seg, packed.pos
    keep = cache is not None
    if not keep:
        cache = init_cache(cfg, 1, layers=1)
        slots = jnp.zeros((1,), jnp.int32)
        # every prompt's state into the scratch; a prompt's first chunk
        # still carries nothing in (`posn` 0), whatever the slot
        seg = jnp.minimum(seg, 0)
    x = _embed(params, jnp.pad(tokens, ((0, 0), (0, pad))), cfg.dtype)
    x = x.astype(cfg.dtype)

    def body(carry, inputs):
        x, state, keysum = carry
        li, layer = inputs
        with jax.named_scope("retention_attn"):
            q, k, v, g = _qkvg(
                cfg, layer, x, lambda t: _rope(t, cfg.rope_theta, pos=posn))
            o, state, keysum = _ret.retention_prefill(
                q[0], k[0], v[0], g[0], seg, posn, slots, state, keysum,
                li if keep else 0, chunk=chunk, eps=cfg.retention_eps,
                kernel=kernel, interpret=interpret)
        return (_out(cfg, layer, x, o[None]), state, keysum), None

    (x, *cache), _ = lax.scan(
        body, (x, *cache),
        (jnp.arange(cfg.n_layers, dtype=jnp.int32), dict(params["blocks"])))
    x = x[:, :T] if packed is None else x[:, packed.last]
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    return _lm_head(x, params, cfg.dtype), tuple(cache)


# ----------------------------------------------------------------------
# decode: one step off the state
# ----------------------------------------------------------------------
def init_pending(cfg: BrumbyConfig, slots: int, held: int) -> _ret.Pending:
    """An empty `retention.Pending` for every layer (each leaf `[L,
    ...]`, `n` too): room for `held` tokens a row, a decode chunk's
    steps but its last."""
    k, v, G = _ret.pending_shapes(cfg.n_layers, slots, cfg.n_kv_heads,
                                  cfg.head_dim, held)
    return _ret.Pending(jnp.zeros(k, cfg.dtype), jnp.zeros(v, cfg.dtype),
                        jnp.zeros(G, F32),
                        jnp.zeros((cfg.n_layers,), jnp.int32))


def decode_step(cfg: BrumbyConfig, params: Dict, token: jax.Array, cache,
                pos, *, live=None, kernel: bool = False,
                interpret: bool = False):
    """One decode step at per-row positions: token [B], pos [B] (the
    position the token sits at, where it is rotated), `cache` =
    (`state`, `keysum`) with row b's state in slot b.  `live` [B] bool
    (the engine's `pos < stop`; None: every row): a row that is not
    live leaves its state as it was and yields zeros for attention.
    Returns (logits [B, vocab] float32, cache).  It is `chunk_step` as
    a chunk of one: nothing held, the state written at once."""
    logits, cache, _ = chunk_step(
        cfg, params, token, cache, init_pending(cfg, token.shape[0], 0), pos,
        last=True, live=live, kernel=kernel, interpret=interpret)
    return logits, cache


def chunk_step(cfg: BrumbyConfig, params: Dict, token: jax.Array, cache,
               pending: _ret.Pending, pos, *, last: bool, live=None,
               kernel: bool = False, interpret: bool = False):
    """One step of a decode CHUNK whose state is written once, at its
    last step; the arguments are `decode_step`'s, and `pending`
    (`init_pending`) holds the chunk's tokens so far.  `last` False:
    the state is only READ, and the token's keys, values and gates are
    held in `pending`.  `last` True: the state takes everything held
    and this token, once (the flush), and `pending` comes back empty.
    Returns (logits, cache, pending)."""
    B = token.shape[0]
    if live is None:
        live = jnp.ones((B,), bool)
    x = _embed(params, token, cfg.dtype)[:, None, :].astype(cfg.dtype)
    kw = dict(eps=cfg.retention_eps, kernel=kernel, interpret=interpret)

    def body(carry, inputs):
        x, state, keysum = carry
        li, layer, held = inputs        # `held`: this layer's `Pending`
        with jax.named_scope("retention_attn"):
            q, k, v, g = _qkvg(
                cfg, layer, x, lambda t: _rope_at(t, cfg.rope_theta, pos))
            if last:
                o, state, keysum = _ret.retention_decode(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], state, keysum, live,
                    li, pending=held, **kw)
                held = held._replace(n=jnp.zeros_like(held.n))
            else:
                o, held = _ret.retention_read(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], state, keysum, held,
                    live, li, **kw)
        return (_out(cfg, layer, x, o[:, None]), state, keysum), held

    (x, *cache), pending = lax.scan(
        body, (x, *cache),
        (jnp.arange(cfg.n_layers, dtype=jnp.int32), dict(params["blocks"]),
         pending))
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    return _lm_head(x[:, 0, :], params, cfg.dtype), tuple(cache), pending
