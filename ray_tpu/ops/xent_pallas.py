"""Pallas fused lm-head + softmax cross-entropy (blockwise vocab,
online logsumexp).

The loss and its gradients are computed without EVER materializing the
[N, V] logits in HBM: the forward walks vocab blocks with an online
(max, sumexp) carry held in VMEM scratch; the backward recomputes each
logits block and contracts it immediately — dx accumulates in a VMEM
[block_n, E] scratch across the vocab-minor grid, dW in a VMEM
[block_v, E] scratch across the rows-minor grid, so neither gradient
pays per-block HBM accumulator round trips (the weakness of the
`lax.scan` row-chunk formulation in `ops/xent.py`, whose dW
accumulator travels through HBM every chunk).

When to use which (measured on v5e-1, PERF.md round 5):
- logits FIT in HBM (the 124M bench: [35840, 50257] bf16 = 3.6 GB):
  the stock lse-form loss is best — XLA stores bf16 logits once and
  skips the backward recompute; the lm-head is MXU-bound there, so
  trading HBM for recompute FLOPs LOSES.
- logits DO NOT fit (long sequences / big vocab): the recompute is
  forced on every formulation, and this kernel's VMEM-resident
  accumulators + double-buffered DMA beat the scan fallback.

Reference counterpart: torch `F.cross_entropy` over materialized
logits (the reference never fuses this); design per
/opt/skills/guides/pallas_guide.md.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

_NEG = -1e30


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _fwd_kernel(x_ref, w_ref, tg_ref, lse_ref, tgt_ref,
                m_scr, l_scr, t_scr, *, v_actual: int, block_v: int):
    import jax.lax as lax
    from jax.experimental import pallas as pl

    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        t_scr[...] = jnp.zeros_like(t_scr)

    s = lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_n, block_v]
    cols = vb * block_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < v_actual, s, _NEG)
    t_scr[...] += jnp.sum(
        jnp.where(cols == tg_ref[...], s, 0.0), axis=1, keepdims=True
    )
    m_new = jnp.maximum(m_scr[...], jnp.max(s, axis=1, keepdims=True))
    l_scr[...] = (
        l_scr[...] * jnp.exp(m_scr[...] - m_new)
        + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True)
    )
    m_scr[...] = m_new

    @pl.when(vb == pl.num_programs(1) - 1)
    def _fin():
        lse_ref[...] = m_scr[...] + jnp.log(l_scr[...])
        tgt_ref[...] = t_scr[...]


def _dx_kernel(x_ref, w_ref, tg_ref, lse_ref, dx_ref, acc_scr,
               *, v_actual: int, block_v: int):
    import jax.lax as lax
    from jax.experimental import pallas as pl

    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    cols = vb * block_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    p = jnp.where(cols < v_actual, jnp.exp(s - lse_ref[...]), 0.0)
    dl = p - jnp.where(cols == tg_ref[...], 1.0, 0.0)
    acc_scr[...] += lax.dot_general(
        dl.astype(x_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_n, E]

    @pl.when(vb == pl.num_programs(1) - 1)
    def _fin():
        dx_ref[...] = acc_scr[...]


def _dw_kernel(w_ref, x_ref, tg_ref, lse_ref, dw_ref, acc_scr,
               *, v_actual: int, n_actual: int, block_v: int,
               block_n: int):
    import jax.lax as lax
    from jax.experimental import pallas as pl

    vb = pl.program_id(0)
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_n, block_v]
    cols = vb * block_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    rows = nb * block_n + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    p = jnp.where(cols < v_actual, jnp.exp(s - lse_ref[...]), 0.0)
    dl = p - jnp.where(cols == tg_ref[...], 1.0, 0.0)
    dl = jnp.where(rows < n_actual, dl, 0.0)  # padded rows contribute 0
    acc_scr[...] += lax.dot_general(
        dl.astype(x_ref.dtype), x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_v, E]

    @pl.when(nb == pl.num_programs(1) - 1)
    def _fin():
        dw_ref[...] = acc_scr[...]


def _prep(x, w, targets, block_n, block_v):
    N, E = x.shape
    V = w.shape[0]
    Np, Vp = _pad_to(N, block_n), _pad_to(V, block_v)
    xc = x
    tg = targets
    if Np != N:
        xc = jnp.pad(x, ((0, Np - N), (0, 0)))
        tg = jnp.pad(targets, (0, Np - N), constant_values=-1)
    wc = w.astype(x.dtype)
    if Vp != V:
        wc = jnp.pad(wc, ((0, Vp - V), (0, 0)))
    return xc, wc, tg.reshape(-1, 1), N, V, Np, Vp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def pallas_cross_entropy(x, w, targets, block_n: int = 512,
                         block_v: int = 512, interpret: bool = False):
    """Mean softmax cross-entropy of rows of `x` against classes of
    `w`, never materializing [N, V] logits in HBM.

    x: [N, E] (bf16/f32), w: [V, E] (f32 master ok), targets: [N]
    int32.  Returns scalar f32 mean loss.  Gradients flow to x and w.
    Default blocks fit double-buffered VMEM for f32 inputs at E<=1024;
    block_v=1024 is ~96 KB over the 16 MB scoped-vmem limit with f32
    blocks (and measured no faster with bf16 ones).  Compiled for the
    TPU unless the caller passes `interpret=True` (the CPU tests).
    """
    loss, _ = _fwd(x, w, targets, block_n, block_v, interpret)
    return loss


def _lse_tgt(x, w, targets, block_n, block_v, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    xc, wc, tg2, N, V, Np, Vp = _prep(x, w, targets, block_n, block_v)
    E = x.shape[1]
    grid = (Np // block_n, Vp // block_v)
    lse, tgt = pl.pallas_call(
        functools.partial(_fwd_kernel, v_actual=V, block_v=block_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, E), lambda n, v: (n, 0)),
            pl.BlockSpec((block_v, E), lambda n, v: (v, 0)),
            pl.BlockSpec((block_n, 1), lambda n, v: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda n, v: (n, 0)),
            pl.BlockSpec((block_n, 1), lambda n, v: (n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(xc, wc, tg2)
    return lse, tgt, (xc, wc, tg2, N, V, Np, Vp)


def _fwd(x, w, targets, block_n, block_v, interpret):
    lse, tgt, (xc, wc, tg2, N, V, Np, Vp) = _lse_tgt(
        x, w, targets, block_n, block_v, interpret
    )
    loss = jnp.mean(lse[:N, 0] - tgt[:N, 0])
    return loss, (x, w, targets, lse)


def _bwd(block_n, block_v, interpret, res, g):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x, w, targets, lse = res
    xc, wc, tg2, N, V, Np, Vp = _prep(x, w, targets, block_n, block_v)
    E = x.shape[1]
    scale = (g / N).astype(jnp.float32)

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, v_actual=V, block_v=block_v),
        grid=(Np // block_n, Vp // block_v),
        in_specs=[
            pl.BlockSpec((block_n, E), lambda n, v: (n, 0)),
            pl.BlockSpec((block_v, E), lambda n, v: (v, 0)),
            pl.BlockSpec((block_n, 1), lambda n, v: (n, 0)),
            pl.BlockSpec((block_n, 1), lambda n, v: (n, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, E), lambda n, v: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, E), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, E), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(xc, wc, tg2, lse)

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, v_actual=V, n_actual=N,
                          block_v=block_v, block_n=block_n),
        grid=(Vp // block_v, Np // block_n),
        in_specs=[
            pl.BlockSpec((block_v, E), lambda v, n: (v, 0)),
            pl.BlockSpec((block_n, E), lambda v, n: (n, 0)),
            pl.BlockSpec((block_n, 1), lambda v, n: (n, 0)),
            pl.BlockSpec((block_n, 1), lambda v, n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, E), lambda v, n: (v, 0)),
        out_shape=jax.ShapeDtypeStruct((Vp, E), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_v, E), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(wc, xc, tg2, lse)

    dx = (dx[:N] * scale).astype(x.dtype)
    dw = (dw[:V] * scale).astype(w.dtype)
    return dx, dw, None


pallas_cross_entropy.defvjp(_fwd, _bwd)


def reference_cross_entropy(x, w, targets) -> jax.Array:
    """Materializing lse-form loss (the testing oracle)."""
    logits = (x @ w.astype(x.dtype).T).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    t = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - t)
