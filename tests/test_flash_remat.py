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


# ---------------------------------------------------------------------
# (v) a window and grouped heads, forward and both backwards, against
# dense attention; and what a caller holds of the grouped call
# ---------------------------------------------------------------------
def _dense_window(q, k, v, window):
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    below = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    live = below >= 0 if window is None else (below >= 0) & (below < window)
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("block", [64, 16], ids=["fused-bwd", "split-bwd"])
@pytest.mark.parametrize("heads", [(8, 2), (4, 4)], ids=["grouped", "equal"])
@pytest.mark.parametrize("window", [None, 8, 24, 64])
def test_window_and_grouped_heads_give_dense_attentions_results(
        window, heads, block):
    """T 64 under the fused backward's limit (block 64) and over it
    (block 16: the split pair, four tiles a side, so whole tiles lie
    outside a window of 8 or 24 and are skipped)."""
    (H, KV), T, D = heads, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (2, T, H, D))
    k = jax.random.normal(ks[1], (2, T, KV, D))
    v = jax.random.normal(ks[2], (2, T, KV, D))
    do = jax.random.normal(ks[3], (2, T, H, D))
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, True, block, block, True, window)
    dense = lambda q, k, v: _dense_window(q, k, v, window)  # noqa: E731
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * do), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_the_fused_backward_walks_a_windows_strips():
    """T 512 in one tile: sub-tiles of 256, so the strips of `_strips`
    (crossed by the diagonal, plain, crossed by the window's lower
    edge, skipped) all occur at a window of 100 and of 300."""
    from ray_tpu.ops import attention as A

    assert A._strips(0, 256, 2, None) == [(0, 256, True), (256, 512, False)]
    for j in range(4):  # no window: `_col_walk`'s crossed, then plain
        vis, full = A._col_walk(j * 256, 256, 256, 4)
        assert A._strips(j * 256, 256, 4, None) == [
            s for s in ((vis * 256, full * 256, True),
                        (full * 256, 1024, False)) if s[0] < s[1]]
    assert A._strips(0, 256, 2, 100) == [(0, 512, True)]
    assert A._strips(0, 256, 4, 600) == [(0, 256, True), (256, 512, False),
                                         (512, 1024, True)]
    assert A._strips(512, 256, 4, 100) == [(512, 1024, True)]
    T, D = 512, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, T, 2, D))
    kv = jax.random.normal(ks[1], (1, T, 1, D))
    do = jax.random.normal(ks[2], (1, T, 2, D))
    for window in (100, 300):
        got = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, True, 512, 512, True, window) * do), (0, 1, 2))(q, kv, kv)
        want = jax.grad(lambda *a: jnp.sum(_dense_window(*a, window) * do),
                        (0, 1, 2))(q, kv, kv)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("window,steps", [(None, (4, 8)), (24, (3, 5)),
                                          (8, (2, 3))])
def test_tiles_outside_the_window_are_not_walked(window, steps):
    """The grid's kv axis is as long as the tiles ONE q block can see:
    at T 64, q blocks of 8 and kv blocks of 16, four / three / two."""
    from ray_tpu.ops import attention as A

    assert A.span_steps(64, 8, 16, window) == steps


def test_the_grouped_call_keeps_no_lane_padded_lse():
    """The forward kernel gives the rows' log-sum-exp as ROWS of lanes
    (`[B*KV, T / block_q, 1, G * block_q]`), and a caller holds it as
    `[B*KV, G, T]`: nothing it holds ends in a dimension of 1."""
    B, T, H, KV, D = 2, 64, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, D), jnp.bfloat16)
    k = q[:, :, :KV]
    _, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, True, 16, 16, True, 24), q, k, k)
    held = jax.tree.leaves(vjp)
    assert [a.shape for a in held if a.dtype == jnp.float32] == [
        (B * KV, H // KV, T)]
    assert all(a.shape[-1] != 1 for a in held)


def test_grouped_heads_and_a_window_are_causal_only():
    q = jnp.zeros((1, 16, 4, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q[:, :, :2], q[:, :, :2], False, 16, 16, True)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q[:, :, :3], q[:, :, :3], True, 16, 16, True)


GPT2_STEP_JAXPR = (
    "134d7fe0bc6f5eb1ee7cd3a387679a813037776ab55e26066abad53707b4565c")


def test_gpt2s_train_step_is_the_program_it_was():
    """`window=None` and equal heads take the kernels that were there:
    the jaxpr of GPT-2's whole train step with the flash kernels in it
    (their bodies are printed with the calls), source positions cut
    out, hashes to what the tree before the grouped kernels gave (PR
    61: the parent's and the change's text compared equal; a later
    change to GPT-2's step or kernels on purpose brings a new hash)."""
    import hashlib

    import optax

    cfg = gpt2.GPT2Config(vocab_size=512, n_positions=1024, n_embd=128,
                          n_layer=2, n_head=2, attention="flash",
                          logits_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: gpt2.init_params(cfg,
                                                     jax.random.PRNGKey(0)))
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(3e-4))
    text = str(jax.make_jaxpr(gpt2.make_train_step(cfg, opt))(
        params, jax.eval_shape(opt.init, params),
        jax.ShapeDtypeStruct((2, 1025), jnp.int32)))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    text = re.sub(r"/[^\s:]*\.py", ".py", text)
    text = re.sub(r"\.py:\d+", ".py", text)
    assert hashlib.sha256(text.encode()).hexdigest() == GPT2_STEP_JAXPR
