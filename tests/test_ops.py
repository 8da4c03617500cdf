"""Kernel/op tests: flash attention (interpret mode on CPU) and MoE
with expert parallelism on the virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention, flash_attention
from ray_tpu.parallel.moe import MoEConfig, init_moe, moe_forward
from ray_tpu.parallel.ring_attention import plain_attention


def _qkv(B=2, T=64, H=4, D=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, dtype) * 0.3 for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_plain(causal):
    q, k, v = _qkv()
    ref = plain_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, 32, 32, True)  # interpret mode
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_flash_attention_grad_matches_plain():
    q, k, v = _qkv(T=32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 16, 16, True) ** 2)

    def f_plain(q, k, v):
        return jnp.sum(plain_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_plain, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )


@pytest.mark.parametrize("bq,bk", [(16, 32), (32, 16), (16, 64), (64, 32)])
def test_flash_attention_grad_rect_blocks(bq, bk):
    """Rectangular blocks exercise the causal block-skip predicates and
    cross-block online-softmax carries in both backward kernels; with
    one block as long as the sequence the forward keeps no state."""
    q, k, v = _qkv(T=64)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, bq, bk, True) ** 2)

    def f_plain(q, k, v):
        return jnp.sum(plain_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_plain, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )


def test_flash_attention_bf16():
    q, k, v = _qkv(T=64, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, True, 32, 32, True)
    ref = plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_attention_refuses_odd_shapes():
    """A shape the kernels cannot tile is an error naming the shape —
    never a quiet switch to the XLA path."""
    q, k, v = _qkv(T=60, D=12)  # D not a multiple of 8
    with pytest.raises(ValueError, match=r"\(2, 60, 4, 12\)"):
        flash_attention(q, k, v, True, 32, 32, True)
    q, k, v = _qkv(T=96, D=16)  # T not a multiple of the blocks
    with pytest.raises(ValueError, match=r"\(2, 96, 4, 16\)"):
        jax.grad(lambda q: flash_attention(q, k, v, True, 64, 64, True).sum())(q)


def test_flash_attention_under_a_mesh_runs_per_shard(monkeypatch):
    """`select_attention("flash", mesh=...)`: the kernel is a custom
    call the SPMD partitioner cannot split, so it is placed per shard
    (batch over the data axes, heads over tp) with shard_map — same
    values and gradients as dense attention on the whole arrays.  The
    test steers the kernel into interpret mode; the program's own call
    is the compiled one (`tests/test_aot_tpu_compile.py`)."""
    import ray_tpu.ops as ops
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.parallel.ring_attention import select_attention

    real = ops.flash_attention
    monkeypatch.setattr(
        ops, "flash_attention",
        lambda q, k, v, causal: real(q, k, v, causal, 32, 32, True))
    mesh = MeshSpec(fsdp=2, tp=2).build(jax.devices()[:4])
    q, k, v = _qkv(B=4, T=32, H=4, D=16)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) ** 2)

    flash = loss(lambda q, k, v: select_attention("flash", q, k, v, mesh))
    dense = loss(lambda q, k, v: plain_attention(q, k, v, causal=True))
    with mesh:
        got, g_got = jax.jit(jax.value_and_grad(flash, (0, 1, 2)))(q, k, v)
    want, g_want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_moe_local_forward_and_grad():
    cfg = MoEConfig(dim=32, hidden=64, num_experts=4, top_k=2,
                    dtype=jnp.float32)
    params = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    out, aux = moe_forward(cfg, params, x)
    assert out.shape == x.shape
    assert np.isfinite(float(aux["load_balance_loss"]))

    def loss(p):
        o, a = moe_forward(cfg, p, x)
        return jnp.mean(o ** 2) + 0.01 * a["load_balance_loss"]

    grads = jax.grad(loss)(params)
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all()


def test_moe_expert_parallel_matches_local():
    """EP dispatch over 4 devices must agree with the local path on the
    same weights (same capacity per token shard)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg = MoEConfig(dim=16, hidden=32, num_experts=4, top_k=1,
                    capacity_factor=4.0, dtype=jnp.float32)
    params = init_moe(cfg, jax.random.PRNGKey(0))
    devices = np.array(jax.devices("cpu")[:4]).reshape(4)
    mesh = Mesh(devices, ("ep",))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16), jnp.float32)

    out_ep, aux_ep = moe_forward(cfg, params, x, mesh)
    assert out_ep.shape == x.shape
    assert np.isfinite(np.asarray(out_ep)).all()
    # per-shard local computation as the oracle: run the local path on
    # each batch shard independently (capacity is per-shard in EP mode)
    outs = []
    for i in range(4):
        o, _ = moe_forward(cfg, params, x[i:i + 1])
        outs.append(np.asarray(o))
    np.testing.assert_allclose(
        np.asarray(out_ep), np.concatenate(outs), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("bq,bk", [(32, 32), (16, 32), (32, 16), (16, 64)])
def test_flash_attention_grad_split_noncausal(bq, bk):
    """Past the fused kernel's limit a call that is NOT causal takes
    the same split pair (a group of one head in the rows): every q block
    walks every kv block, and none is masked."""
    q, k, v = _qkv(T=64)
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, False, bq, bk, True) * w)

    def f_plain(q, k, v):
        return jnp.sum(plain_attention(q, k, v, causal=False) * w)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_plain, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad_fused_single_tile(causal):
    """blocks == T dispatches the FUSED single-tile backward (one
    kernel computing dq/dk/dv with in-kernel delta) — the bench-shape
    path; must match dense gradients like the split kernels do."""
    q, k, v = _qkv(T=32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 32, 32, True) ** 2)

    def f_plain(q, k, v):
        return jnp.sum(plain_attention(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_plain, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )


# ----------------------------------------------------------------------
# the in-tile walk: a grid step's tile is walked in sub-tiles, those
# above the diagonal skipped, only those it crosses masked
# ----------------------------------------------------------------------
def test_causal_walk_counts_at_the_train_cell_shape():
    """`gpt2m_train_stream`: T 1024 as ONE tile.  The counts are the
    loop bounds the kernels run (`_row_walk` forward, `_col_walk`
    backward; `causal_walk` asserts the two views agree)."""
    walk = attention.causal_walk
    assert walk(1024, 1024, 128, 128) == dict(visited=36, masked=8, total=64)
    assert walk(1024, 1024, 256, 256) == dict(visited=10, masked=4, total=16)
    assert walk(1024, 1024, 512, 512) == dict(visited=3, masked=2, total=4)
    sub = attention._sub_tile(1024)  # what the kernels pick there
    assert walk(1024, 1024, sub, sub) == dict(visited=10, masked=4, total=16)
    # a grid tile wholly below the diagonal masks nothing, one wholly
    # above it visits nothing, and rectangular sub-tiles count too
    assert walk(1024, 1024, 256, 256, 1024, 0) == dict(
        visited=16, masked=0, total=16)
    assert walk(1024, 1024, 256, 256, 0, 1024)["visited"] == 0
    assert walk(512, 512, 256, 128) == dict(visited=6, masked=4, total=8)
    # the fused backward walks the same sub-tiles kv sub-block by kv
    # sub-block (`_col_walk`): both views count alike
    for bq, bk, sq, sk, q0 in [(1024, 1024, 256, 256, 0), (512, 512, 256, 128, 0),
                               (512, 512, 128, 256, 0), (512, 512, 128, 128, 512)]:
        cols = [attention._col_walk(j * sk - q0, sk, sq, bq // sq)
                for j in range(bk // sk)]
        got = walk(bq, bk, sq, sk, q0, 0)
        assert got["visited"] == sum(bq // sq - vis for vis, _ in cols)
        assert got["masked"] == sum(full - vis for vis, full in cols)
    # a block the walk cannot cut is walked whole
    assert (attention._sub_tile(96), attention._sub_tile(32)) == (96, 32)


_WALK_CASES = {
    # T, H, D, dtype, causal, block: one tile holding skipped, unmasked
    # and diagonal sub-tiles at once, at both head sizes and dtypes
    "t512-d64-f32": (512, 2, 64, jnp.float32, True, 512),
    "t512-d128-bf16": (512, 1, 128, jnp.bfloat16, True, 512),
    "t512-d64-bf16": (512, 2, 64, jnp.bfloat16, True, 512),
    "t256-d128-f32": (256, 1, 128, jnp.float32, True, 256),
    "t512-d64-f32-noncausal": (512, 1, 64, jnp.float32, False, 512),
    # a multi-block grid (forward walk with a moving origin, split
    # backward): tiles below, on and above the diagonal
    "t1024-grid512-d64-f32": (1024, 1, 64, jnp.float32, True, 512),
    "t768-grid256-d64-bf16": (768, 1, 64, jnp.bfloat16, True, 256),
}


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_flash_attention_walk_matches_plain(case):
    """Output AND gradients against `plain_attention` where a tile is
    walked in several sub-tiles."""
    T, H, D, dtype, causal, block = _WALK_CASES[case]
    assert block // attention._sub_tile(block) >= 2
    q, k, v = _qkv(B=1, T=T, H=H, D=D, seed=3, dtype=dtype)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * w)

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal, block, block, True))
    plain = loss(lambda q, k, v: plain_attention(q, k, v, causal=causal))
    out = flash_attention(q, k, v, causal, block, block, True)
    ref = plain_attention(q, k, v, causal=causal)
    g1 = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        rtol=tol, atol=tol)
    gtol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32),
            rtol=gtol, atol=gtol)
