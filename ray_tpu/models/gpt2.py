"""GPT-2 family, TPU-first.

The flagship model for the Train-equivalent (BASELINE config #2: GPT-2
124M pretraining).  Written as explicit param pytrees + pure functions
(idiomatic jax: transforms compose over it freely) with a parallel
*logical axis* tree so the same model runs under any mesh rule table —
DP, FSDP, TP, SP are sharding choices, not model edits (SURVEY §2.5).

TPU notes:
- matmuls run in bfloat16 against f32 master weights (MXU native);
- attention can be dense, ring (sequence-parallel over `sp`, long
  context), or Ulysses all-to-all — config flag, same weights;
- blocks are scanned (`lax.scan` over stacked layer params) so XLA
  compiles ONE block body regardless of depth — compile time stays flat
  and remat (`jax.checkpoint`) applies per-block.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import checkpoint_block
from ray_tpu.parallel.ring_attention import plain_attention, select_attention


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0  # pretraining default; applied only if >0
    dtype: Any = jnp.bfloat16  # compute dtype (params stay f32)
    attention: str = "dense"  # dense | flash | ring | ulysses
    remat: bool = True
    # lm-head logits dtype for the LOSS path: float32 (default) or
    # bfloat16 — bf16 halves the [B, T, vocab] HBM traffic (the
    # single largest tensor in the step) at ~1e-3 loss precision;
    # `forward()` always returns f32 logits for inference callers
    logits_dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @staticmethod
    def gpt2_124m() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def tiny(vocab_size: int = 512) -> "GPT2Config":
        return GPT2Config(
            vocab_size=vocab_size, n_positions=128, n_embd=64, n_layer=2, n_head=4
        )


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------
def init_params(cfg: GPT2Config, key: jax.Array) -> Dict:
    """Stacked-block layout: block params have a leading n_layer dim so
    the forward pass scans over them."""
    k = jax.random.split(key, 8)
    std = 0.02
    L, E, H = cfg.n_layer, cfg.n_embd, 4 * cfg.n_embd
    proj_std = std / math.sqrt(2 * cfg.n_layer)

    def n(key, shape, s=std):
        return jax.random.normal(key, shape, dtype=jnp.float32) * s

    return {
        "wte": n(k[0], (cfg.vocab_size, E)),
        "wpe": n(k[1], (cfg.n_positions, E), 0.01),
        "blocks": {
            "ln1_g": jnp.ones((L, E)),
            "ln1_b": jnp.zeros((L, E)),
            "attn_qkv_w": n(k[2], (L, E, 3 * E)),
            "attn_qkv_b": jnp.zeros((L, 3 * E)),
            "attn_out_w": n(k[3], (L, E, E), proj_std),
            "attn_out_b": jnp.zeros((L, E)),
            "ln2_g": jnp.ones((L, E)),
            "ln2_b": jnp.zeros((L, E)),
            "mlp_fc_w": n(k[4], (L, E, H)),
            "mlp_fc_b": jnp.zeros((L, H)),
            "mlp_out_w": n(k[5], (L, H, E), proj_std),
            "mlp_out_b": jnp.zeros((L, E)),
        },
        "lnf_g": jnp.ones((E,)),
        "lnf_b": jnp.zeros((E,)),
    }


def logical_axes(cfg: GPT2Config) -> Dict:
    """Logical-axis tree matching init_params; mapped to mesh axes by
    `ray_tpu.parallel.sharding` rules (leading None = stacked layer dim)."""
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": {
            "ln1_g": (None, "embed"),
            "ln1_b": (None, "embed"),
            "attn_qkv_w": (None, "embed", "heads"),
            "attn_qkv_b": (None, "heads"),
            "attn_out_w": (None, "heads", "embed"),
            "attn_out_b": (None, "embed"),
            "ln2_g": (None, "embed"),
            "ln2_b": (None, "embed"),
            "mlp_fc_w": (None, "embed", "mlp"),
            "mlp_fc_b": (None, "mlp"),
            "mlp_out_w": (None, "mlp", "embed"),
            "mlp_out_b": (None, "embed"),
        },
        "lnf_g": ("embed",),
        "lnf_b": ("embed",),
    }


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
# `jax.named_scope`s (`embed`, `attn`, `mlp`, `norm`, `lm_head`) put the
# block part an op came from into its metadata, which is what a device
# trace prints; they change nothing that is computed.
def _layer_norm(x, g, b, eps=1e-5):
    with jax.named_scope("norm"):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + eps) * g + b


def backbone(cfg: GPT2Config, params: Dict, tokens: jax.Array,
             mesh=None) -> jax.Array:
    """tokens [B, T] int32 -> final hidden states [B, T, embd] (compute
    dtype), i.e. everything up to (not including) the lm-head matmul."""
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = (params["wte"].astype(cfg.dtype)[tokens]
             + params["wpe"].astype(cfg.dtype)[:T])

    def block(x, layer_params):
        # layer_params: one layer's slice of every block param, closed
        # over by `one` (as arguments of the checkpointed function they
        # reorder the backward loop's operands: another program text)
        def one(x):
            with jax.named_scope("attn"):
                h = _layer_norm(
                    x,
                    layer_params["ln1_g"].astype(cfg.dtype),
                    layer_params["ln1_b"].astype(cfg.dtype),
                )
                B_, T_, E = x.shape
                qkv = (h @ layer_params["attn_qkv_w"].astype(cfg.dtype)
                       + layer_params["attn_qkv_b"].astype(cfg.dtype))
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = q.reshape(B_, T_, cfg.n_head, cfg.head_dim)
                k = k.reshape(B_, T_, cfg.n_head, cfg.head_dim)
                v = v.reshape(B_, T_, cfg.n_head, cfg.head_dim)
                o = select_attention(cfg.attention, q, k, v, mesh, causal=True)
                x1 = x + (
                    o.reshape(B_, T_, E)
                    @ layer_params["attn_out_w"].astype(cfg.dtype)
                    + layer_params["attn_out_b"].astype(cfg.dtype)
                )
            with jax.named_scope("mlp"):
                h2 = _layer_norm(
                    x1,
                    layer_params["ln2_g"].astype(cfg.dtype),
                    layer_params["ln2_b"].astype(cfg.dtype),
                )
                h2 = (h2 @ layer_params["mlp_fc_w"].astype(cfg.dtype)
                      + layer_params["mlp_fc_b"].astype(cfg.dtype))
                h2 = jax.nn.gelu(h2)
                h2 = (h2 @ layer_params["mlp_out_w"].astype(cfg.dtype)
                      + layer_params["mlp_out_b"].astype(cfg.dtype))
            return x1 + h2

        # remat recomputes the whole block in the backward pass but the
        # flash kernel's two results, which `checkpoint_block` keeps
        return (checkpoint_block(one) if cfg.remat else one)(x), None

    x, _ = lax.scan(block, x.astype(cfg.dtype), params["blocks"])
    return _layer_norm(
        x, params["lnf_g"].astype(cfg.dtype), params["lnf_b"].astype(cfg.dtype)
    )


def lm_head(cfg: GPT2Config, params: Dict, x: jax.Array,
            out_dtype=jnp.float32) -> jax.Array:
    """Weight-tied projection to vocab logits — the ONE definition both
    the training loss and inference share."""
    with jax.named_scope("lm_head"):
        return (x @ params["wte"].astype(cfg.dtype).T).astype(out_dtype)


def forward(cfg: GPT2Config, params: Dict, tokens: jax.Array,
            mesh=None) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab] (f32)."""
    return lm_head(cfg, params, backbone(cfg, params, tokens, mesh))


def loss_fn(cfg: GPT2Config, params: Dict, tokens: jax.Array,
            mesh=None) -> jax.Array:
    """Next-token cross entropy; tokens [B, T+1] (shift done here).

    Uses the lse-reduction form: XLA fuses the logsumexp into the
    lm-head matmul's epilogue, so the [B, T, vocab] *log-prob* tensor
    never materializes (the logits do, transiently).  The lm-head is
    MXU-bound at these widths and XLA stores bf16 logits once; forms
    that never materialize the logits (a scan over chunks, a blockwise
    Pallas kernel) recompute the lm-head matmul in the backward, which
    cost more than the HBM they saved when both were tried
    (`docs/perf_history.md`), so neither is kept.
    """
    inputs = tokens[:, :-1]
    targets = tokens[:, 1:]
    x = backbone(cfg, params, inputs, mesh)
    logits = lm_head(cfg, params, x, out_dtype=cfg.logits_dtype)
    # reductions in f32 regardless of the logits' storage dtype (XLA
    # fuses the upcast into the reduce: no f32 materialization)
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1
    )
    tgt = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    )[..., 0].astype(jnp.float32)
    return jnp.mean(lse - tgt)


def num_params(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


# ----------------------------------------------------------------------
# train step
# ----------------------------------------------------------------------
def make_train_step(cfg: GPT2Config, optimizer, mesh=None):
    """Returns step(params, opt_state, tokens) -> (params, opt_state,
    metrics).  Pure; callers jit it with shardings."""

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, mesh)
        )(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        gnorm = jnp.sqrt(
            sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
        )
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return step


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup_steps: int = 100, total_steps: int = 10_000):
    import optax

    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )
