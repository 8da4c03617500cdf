"""The controls' lower precisions, as hooks for the references'
matmul operands.  A control is the reference itself computed one step
below the precision the configuration states — the step a later PR
would be tempted to take — and the comparison has to fail it."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def fp8_e4m3(x):
    """Per-tensor scaled float8 (e4m3) rounding of a matmul operand,
    straight-through in the backward pass: the forward sees the rounded
    values, cotangents stay float32 (what an fp8 training recipe with
    higher-precision gradients does)."""
    scale = 240.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale
    return x + jax.lax.stop_gradient(q - x)


HOOKS = {"fp8": fp8_e4m3}
