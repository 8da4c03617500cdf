"""Under remat the backward pass's replay does not run the flash
forward kernel again: `_fwd` names the kernel's two results
(`FLASH_RESIDUALS`), `checkpoint_block` keeps exactly those.

Traced cases need no chip and lower nothing (`jax.make_jaxpr`); run
cases take the kernels through the interpreter at tiny shapes, as
`tests/test_ops.py` does.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import gpt2, llama, mixtral
from ray_tpu.ops import FLASH_RESIDUALS, checkpoint_block, flash_attention


def _gpt2(attention):
    cfg = gpt2.GPT2Config(vocab_size=128, n_positions=64, n_embd=32,
                          n_layer=2, n_head=2, attention=attention,
                          remat=True)
    return cfg, gpt2.init_params(cfg, jax.random.PRNGKey(0)), gpt2.loss_fn


def _llama(attention):
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), attention=attention,
                              remat=True)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0)), llama.loss_fn


def _mixtral(attention):
    cfg = dataclasses.replace(mixtral.MixtralConfig.tiny(),
                              attention=attention, remat=True)
    return (cfg, mixtral.init_params(cfg, jax.random.PRNGKey(0)),
            lambda c, p, t: mixtral.loss_fn(c, p, t)[0])


MODELS = {"gpt2": (gpt2, _gpt2), "llama": (llama, _llama),
          "mixtral": (mixtral, _mixtral)}
# `checkpoint_block` less its policy
BARE = functools.partial(jax.checkpoint, prevent_cse=False)


def _grad_jaxpr(make, attention):
    """The text of `jax.grad(loss_fn)`'s jaxpr, traced afresh."""
    cfg, params, loss_fn = make(attention)
    tokens = jnp.zeros((2, 65), jnp.int32)
    jax.clear_caches()
    return str(jax.make_jaxpr(
        jax.grad(lambda p: loss_fn(cfg, p, tokens)))(params))


def _calls(text, kernel):
    return len(re.findall(rf"\bname={kernel}\b", text))


# ---------------------------------------------------------------------
# (i) traced: the forward kernel once a layer body, not twice
# ---------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_replay_does_not_call_the_forward_kernel(name, monkeypatch):
    module, make = MODELS[name]
    kept = _grad_jaxpr(make, "flash")
    # the scan's body is printed once: first pass + the replay's read
    assert _calls(kept, "flash_fwd") == 1, name
    assert _calls(kept, "flash_bwd_fused") == 1, name
    for tag in FLASH_RESIDUALS:
        assert f"name={tag}" in kept
    monkeypatch.setattr(module, "checkpoint_block", BARE)
    bare = _grad_jaxpr(make, "flash")
    assert _calls(bare, "flash_fwd") == 2, name
    assert _calls(bare, "flash_bwd_fused") == 1, name


# ---------------------------------------------------------------------
# (iii) no such name in the block: the policy keeps nothing
# ---------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_dense_block_remats_as_under_a_bare_checkpoint(name, monkeypatch):
    module, make = MODELS[name]
    policy = re.compile(r"policy=.*")  # the one param a jaxpr prints
    kept = _grad_jaxpr(make, "dense")
    assert "save_only_these_names" in kept
    monkeypatch.setattr(module, "checkpoint_block", BARE)
    bare = _grad_jaxpr(make, "dense")
    assert "policy=None" in bare
    assert policy.sub("policy=_", kept) == policy.sub("policy=_", bare)


# ---------------------------------------------------------------------
# (ii) run: a scan of checkpointed blocks, kept against recomputed
# ---------------------------------------------------------------------
def _scan_loss(checkpoint, block):
    B, T, H, D = 2, 64, 2, 16
    E = H * D

    def one(x, w):
        q, k, v = jnp.split(x @ w, 3, axis=-1)
        o = flash_attention(*(a.reshape(B, T, H, D) for a in (q, k, v)),
                            True, block, block, True)
        return x + o.reshape(B, T, E)

    def loss(ws, x):
        x, _ = lax.scan(
            lambda x, w: (checkpoint(lambda x: one(x, w))(x), None), x, ws)
        return jnp.sum(x.astype(jnp.float32) ** 2)

    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (B, T, E), jnp.float32)
    ws = jax.random.normal(kw, (3, E, 3 * E), jnp.float32) / E ** 0.5
    return loss, ws, x


@pytest.mark.parametrize("block", [64, 32], ids=["fused-bwd", "split-bwd"])
def test_kept_results_give_the_recomputed_gradients_exactly(block):
    got = {}
    for label, ckpt in (("kept", checkpoint_block), ("bare", BARE)):
        loss, ws, x = _scan_loss(ckpt, block)
        got[label] = jax.value_and_grad(loss, argnums=(0, 1))(ws, x)
        text = str(jax.make_jaxpr(jax.grad(loss))(ws, x))
        assert _calls(text, "flash_fwd") == (1 if label == "kept" else 2)
    (l_kept, g_kept), (l_bare, g_bare) = got["kept"], got["bare"]
    assert np.array_equal(l_kept, l_bare)
    for a, b in zip(g_kept, g_bare):
        assert np.isfinite(a).all() and np.abs(a).max() > 0
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------
# (iv) without remat a caller holds lse as [B*H, T]
# ---------------------------------------------------------------------
@pytest.mark.parametrize("block", [64, 32], ids=["fused-bwd", "split-bwd"])
def test_the_lse_a_caller_holds_is_two_dimensional(block):
    B, T, H, D = 2, 64, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, D), jnp.bfloat16)
    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, True, block, block, True),
        q, q, q)
    held = jax.tree.leaves(vjp)  # the residuals the closure carries
    f32 = [a.shape for a in held if a.dtype == jnp.float32]
    assert f32 == [(B * H, T)]
    # and nothing it holds is the kernel's lane-padded [B*H, T, 1]
    assert all(a.shape[-1] != 1 for a in held)
