"""Numerics for the Pallas fused lm-head+xent kernel vs the
materializing oracle (CPU interpret mode; the bench exercises it on
hardware)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.xent_pallas import (
    pallas_cross_entropy,
    reference_cross_entropy,
)


@pytest.mark.parametrize("n,e,v,bn,bv", [
    (256, 128, 384, 128, 128),     # exact tiling
    (200, 128, 300, 128, 128),     # row AND vocab padding
    (512, 256, 1000, 256, 256),
])
def test_loss_and_grads_match_reference(n, e, v, bn, bv):
    key = jax.random.PRNGKey(0)
    kx, kw, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n, e), jnp.float32) * 0.5
    w = jax.random.normal(kw, (v, e), jnp.float32) * 0.1
    tg = jax.random.randint(kt, (n,), 0, v, jnp.int32)

    ref_loss, (ref_dx, ref_dw) = jax.value_and_grad(
        reference_cross_entropy, argnums=(0, 1)
    )(x, w, tg)
    loss, (dx, dw) = jax.value_and_grad(
        lambda x_, w_: pallas_cross_entropy(x_, w_, tg, bn, bv, True),
        argnums=(0, 1),
    )(x, w)

    np.testing.assert_allclose(loss, ref_loss, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(dx, ref_dx, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(dw, ref_dw, rtol=2e-3, atol=2e-4)


def test_bf16_inputs():
    key = jax.random.PRNGKey(1)
    kx, kw, kt = jax.random.split(key, 3)
    n, e, v = 256, 128, 512
    x = (jax.random.normal(kx, (n, e), jnp.float32) * 0.5).astype(
        jnp.bfloat16
    )
    w = jax.random.normal(kw, (v, e), jnp.float32) * 0.1
    tg = jax.random.randint(kt, (n,), 0, v, jnp.int32)
    ref = reference_cross_entropy(x, w, tg)
    got = pallas_cross_entropy(x, w, tg, 128, 128, True)
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)
    # grads exist and are finite in the storage dtypes
    dx, dw = jax.grad(
        lambda x_, w_: pallas_cross_entropy(x_, w_, tg, 128, 128, True),
        argnums=(0, 1),
    )(x, w)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    assert bool(jnp.isfinite(dx.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(dw).all())
