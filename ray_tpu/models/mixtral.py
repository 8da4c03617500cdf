"""Mixtral-style sparse-MoE transformer, TPU-first.

The expert-parallel model family (SURVEY §2.5: EP/MoE is absent from
the reference and first-class here).  Architecture = the Llama lineage
(RMSNorm, RoPE, GQA attention — reused from `models/llama.py`) with the
dense SwiGLU MLP replaced by a top-k routed mixture of experts
(`parallel/moe.py`: capacity-slot dispatch, Switch-style load-balance
aux loss, `lax.all_to_all` over the `ep` mesh axis under shard_map).

This is the expert model that TRAINS (capacity slots: a token over an
expert's capacity is dropped).  The expert layer that SERVES through
the engine is the dropless one (`parallel/moe.dropless_moe`, under
`models/deepseek_v3.py`): this file has no prefill or decode.

Same design stance as gpt2/llama: explicit param pytrees + pure
functions, blocks stacked under `lax.scan` (one compiled block body),
logical-axis tree so TP/FSDP/EP are rule-table swaps, bf16 compute.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import _apply, _rms_norm, _rope
from ray_tpu.ops.attention import checkpoint_block
from ray_tpu.parallel.moe import MoEConfig, init_moe, moe_forward
from ray_tpu.parallel.ring_attention import select_attention


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    intermediate: int = 14336  # per-expert hidden
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    router_aux_coef: float = 0.02  # load-balance loss weight
    dtype: Any = jnp.bfloat16
    attention: str = "dense"  # dense | flash | ring | ulysses
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(
            dim=self.dim, hidden=self.intermediate,
            num_experts=self.num_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor, dtype=self.dtype,
        )

    @staticmethod
    def mixtral_8x7b() -> "MixtralConfig":
        return MixtralConfig()

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MixtralConfig":
        return MixtralConfig(
            vocab_size=vocab_size, max_seq_len=128, dim=64, n_layers=2,
            n_heads=4, n_kv_heads=2, intermediate=96, num_experts=4,
            top_k=2,
        )


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------
def init_params(cfg: MixtralConfig, key: jax.Array) -> Dict:
    ka = jax.random.split(key, 6)
    L, E = cfg.n_layers, cfg.dim
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    std = 0.02
    proj_std = std / math.sqrt(2 * L)

    def n(k, shape, s=std):
        return jax.random.normal(k, shape, dtype=jnp.float32) * s

    moe_keys = jax.random.split(ka[5], L)
    moe_layers = [init_moe(cfg.moe, mk) for mk in moe_keys]
    moe_stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *moe_layers)

    return {
        "tok_emb": n(ka[0], (cfg.vocab_size, E)),
        "blocks": {
            "attn_norm": jnp.ones((L, E)),
            "wq": n(ka[1], (L, E, H * hd)),
            "wk": n(ka[2], (L, E, KV * hd)),
            "wv": n(ka[3], (L, E, KV * hd)),
            "wo": n(ka[4], (L, H * hd, E), proj_std),
            "moe_norm": jnp.ones((L, E)),
            **{f"moe_{k}": v for k, v in moe_stacked.items()},
        },
        "final_norm": jnp.ones((E,)),
        "lm_head": n(jax.random.fold_in(ka[0], 1), (E, cfg.vocab_size)),
    }


def logical_axes(cfg: MixtralConfig) -> Dict:
    return {
        "tok_emb": ("vocab", "embed"),
        "blocks": {
            "attn_norm": (None, "embed"),
            "wq": (None, "embed", "heads"),
            "wk": (None, "embed", "heads"),
            "wv": (None, "embed", "heads"),
            "wo": (None, "heads", "embed"),
            "moe_norm": (None, "embed"),
            # expert axis shards over `ep` (rule table maps it)
            "moe_router": (None, "embed", None),
            "moe_w_in": (None, "expert", "embed", "mlp"),
            "moe_w_out": (None, "expert", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def forward(cfg: MixtralConfig, params: Dict, tokens: jax.Array,
            mesh=None) -> Tuple[jax.Array, Dict]:
    """tokens [B, T] int32 -> (logits [B, T, vocab] f32,
    aux {load_balance_loss} averaged over layers)."""
    B, T = tokens.shape
    x = params["tok_emb"].astype(cfg.dtype)[tokens]
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    group = H // KV
    moe_cfg = cfg.moe

    def body(x, layer):
        moe_params = {
            "router": layer["moe_router"],
            "w_in": layer["moe_w_in"],
            "w_out": layer["moe_w_out"],
        }

        def one(xin):
            h = _rms_norm(xin, layer["attn_norm"].astype(cfg.dtype),
                          cfg.norm_eps)
            q = _apply(h, layer["wq"], cfg.dtype)
            k = _apply(h, layer["wk"], cfg.dtype)
            v = _apply(h, layer["wv"], cfg.dtype)
            q = _rope(q.reshape(B, T, H, hd), cfg.rope_theta)
            k = _rope(k.reshape(B, T, KV, hd), cfg.rope_theta)
            v = v.reshape(B, T, KV, hd)
            if group > 1:
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            o = select_attention(cfg.attention, q, k, v, mesh, causal=True)
            o = o.reshape(B, T, H * hd)
            x1 = xin + _apply(o, layer["wo"], cfg.dtype)

            h2 = _rms_norm(x1, layer["moe_norm"].astype(cfg.dtype),
                           cfg.norm_eps)
            moe_out, aux = moe_forward(moe_cfg, moe_params, h2, mesh)
            return x1 + moe_out, aux["load_balance_loss"]

        fn = checkpoint_block(one) if cfg.remat else one
        out, aux_loss = fn(x)
        return out, aux_loss

    x = x.astype(cfg.dtype)
    x, aux_losses = lax.scan(body, x, dict(params["blocks"]))
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return logits, {"load_balance_loss": jnp.mean(aux_losses)}


def loss_fn(cfg: MixtralConfig, params: Dict, tokens: jax.Array,
            mesh=None) -> Tuple[jax.Array, Dict]:
    """Next-token CE + router load-balance aux (reference to the MoE
    literature: Switch/Mixtral train with an aux coefficient)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = forward(cfg, params, inputs, mesh)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - tgt)
    total = ce + cfg.router_aux_coef * aux["load_balance_loss"]
    return total, {"ce_loss": ce, **aux}


def num_params(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


def active_params_per_token(cfg: MixtralConfig, params) -> int:
    """Parameters touched per token (the MoE efficiency headline): all
    non-expert weights + top_k experts' FFNs."""
    total = num_params(params)
    expert_ffn = (
        cfg.n_layers * cfg.num_experts * 2 * cfg.dim * cfg.intermediate
    )
    active_ffn = (
        cfg.n_layers * cfg.top_k * 2 * cfg.dim * cfg.intermediate
    )
    return total - expert_ffn + active_ffn


# ----------------------------------------------------------------------
# train step
# ----------------------------------------------------------------------
def make_train_step(cfg: MixtralConfig, optimizer, mesh=None):
    def step(params, opt_state, tokens):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, mesh), has_aux=True
        )(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        import optax

        params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": loss, **metrics}

    return step
