"""`ops/grouped_matmul.grouped_product`: the grouped product with a
`custom_vjp`, against `jax.grad` of the plain per-group product.  The
kernels run in the interpreter (CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.grouped_matmul import gmm, grouped_product

M, K, N, G, TILE = 256, 128, 256, 5, 64


def _plain(xs, w, sizes):
    """Rows of group g times w[g], zeros past the last group."""
    ends = jnp.cumsum(sizes)
    row = jnp.arange(xs.shape[0])
    out = jnp.zeros((xs.shape[0], w.shape[-1]), jnp.float32)
    for g in range(w.shape[0]):
        mine = (row >= ends[g] - sizes[g]) & (row < ends[g])
        out = out + jnp.where(mine[:, None], xs @ w[g], 0.0)
    return out


def _operands(seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (M, K)),
            jax.random.normal(ks[1], (G, K, N)) * 0.1,
            jax.random.normal(ks[2], (M, N)))


@pytest.mark.parametrize("sizes", [
    [64, 0, 100, 30, 0],     # empty groups, a ragged last tile, a tail
    [10, 20, 30, 40, 50],    # groups that end inside tiles
    [256, 0, 0, 0, 0],       # one group takes every row
    [0, 0, 0, 0, 0],         # no row at all
    [1, 1, 1, 1, 252],
], ids=["empty-and-ragged", "inside-tiles", "one-group", "no-rows", "ones"])
def test_the_vjp_is_the_plain_products_gradient(sizes):
    xs, w, dy = _operands()
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_product(xs, w, sizes, TILE, True)
    np.testing.assert_allclose(got, _plain(xs, w, sizes), atol=1e-5)
    g_got = jax.grad(lambda xs, w: jnp.sum(
        grouped_product(xs, w, sizes, TILE, True) * dy), (0, 1))(xs, w)
    g_want = jax.grad(lambda xs, w: jnp.sum(_plain(xs, w, sizes) * dy),
                      (0, 1))(xs, w)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    # a group with no row has no gradient, a row with no group neither
    empty = np.asarray(sizes) == 0
    assert not np.asarray(g_got[1])[empty].any()
    assert not np.asarray(g_got[0])[int(sizes.sum()):].any()


def test_rows_past_the_last_group_are_zeros_whatever_the_buffer_held():
    """`gmm` leaves such rows as they were; the differentiable product
    zeroes them, forward and backward, so that nothing a buffer held
    reaches a scatter-add (NaNs among them)."""
    xs, w, dy = _operands()
    sizes = jnp.asarray([40, 0, 0, 0, 0], jnp.int32)
    poisoned = xs.at[40:].set(jnp.nan)
    out = grouped_product(poisoned, w, sizes, TILE, True)
    assert np.isfinite(np.asarray(out)).all() and not np.asarray(out)[40:].any()
    dxs = jax.grad(lambda xs: jnp.sum(
        grouped_product(xs, w, sizes, TILE, True) * dy))(poisoned)
    assert not np.asarray(dxs)[40:].any()


def test_the_transposed_walk_multiplies_by_the_matrices_transposed():
    xs, w, _ = _operands()
    sizes = jnp.asarray([64, 64, 0, 64, 64], jnp.int32)  # every row grouped
    got = gmm(xs, jnp.swapaxes(w, 1, 2), sizes, row_tile=TILE,
              interpret=True, transpose_rhs=True)
    want = gmm(xs, w, sizes, row_tile=TILE, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_matrices_gradient_comes_back_in_their_own_dtype():
    """float32 parameters, bfloat16 rows: the product rounds the
    matrices inside, and their gradient is float32 as summed."""
    xs, w, dy = _operands()
    sizes = jnp.asarray([100, 50, 50, 56, 0], jnp.int32)
    dxs, dw = jax.grad(lambda xs, w: jnp.sum(grouped_product(
        xs, w, sizes, TILE, True).astype(jnp.float32) * dy), (0, 1))(
        xs.astype(jnp.bfloat16), w)
    assert dxs.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    want = jax.grad(lambda w: jnp.sum(_plain(
        xs.astype(jnp.bfloat16).astype(jnp.float32),
        w.astype(jnp.bfloat16).astype(jnp.float32), sizes) * dy))(w)
    np.testing.assert_allclose(dw, want, atol=0.15, rtol=0.05)
