"""Grouped matmul over rows sorted by group, the NEXT group's matrix in
flight while the current group's row tiles are computed.

    gmm(xs [M, K], w [G, K, N], group_sizes [G]) -> [M, N]

Rows `sum(group_sizes[:g]) .. sum(group_sizes[:g + 1])` of `xs` times
`w[g]`.  The walk is megablox's (`jax.experimental.pallas.ops.tpu.
megablox`: one grid step a (row tile, group) pair that share a row, a
whole tile computed a step, the rows of other groups masked out of the
store, a group with no row never read), and so is its metadata.  What
differs is WHEN a group's matrix is fetched.  megablox leaves it to the
grid's pipeline, which fetches a step ahead: the next group's `[K, N]`
(7.3 MB at lfm2's widths, ~10 us) is asked for at the current group's
LAST step and hides behind that one step's product (~5.7 us of MXU at
128 rows), so a prefill, whose groups take two or three steps, pays
every group's read in full ON TOP of its products:
`groups x max(step, read) + (steps - groups) x step`, 0.48 ms at 4,096
rows over 32 groups where the read alone is 0.32 and the products 0.36
(PERF.md section 6, PR 35).  Here the matrices stay in HBM and the
kernel copies them itself into two VMEM slots: at a group's FIRST step
it waits for its own matrix and starts the next group's, which then has
all of this group's steps to arrive: `sum over groups of max(steps x
step, read)`.  A decode step, a step a group, gains nothing from it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

def _walk(group_sizes, m: int, tm: int):
    """megablox's walk, and for each of its steps: whether it is its
    group's first, the VMEM slot its group's matrix is in (groups with
    work alternate), and the next group with work (-1 after the last)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    (offsets, gids, tids), steps = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=group_sizes.shape[0], visit_empty_groups=False)
    n = gids.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)
    first = (i < steps) & ((i == 0) | (gids != jnp.roll(gids, 1)))
    slot = (jnp.cumsum(first) - 1) % 2
    # the first step of a later group: the nearest `first` behind step i
    at = lax.cummin(jnp.where(first, i, n), reverse=True)
    after = jnp.concatenate([at[1:], jnp.full((1,), n, at.dtype)])
    nxt = jnp.where(after < n, gids[jnp.minimum(after, n - 1)], -1)
    return (offsets, gids, tids, first.astype(jnp.int32),
            slot.astype(jnp.int32), nxt.astype(jnp.int32)), steps


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret",
                                             "transpose_rhs"))
def gmm(xs, w, group_sizes, *, row_tile: int = 128, interpret: bool = False,
        transpose_rhs: bool = False):
    """`xs` [M, K] (M a multiple of `row_tile`), `w` [G, K, N] with K
    and N whole in one tile, `group_sizes` [G] int32 summing to M or
    less: rows past the last group belong to none, are never computed,
    and come back as whatever was there.  `transpose_rhs`: `w` is `[G,
    N, K]` and a group's rows are multiplied by its matrix transposed
    (the input's gradient of the product with `w`, no copy of `w`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), tm = xs.shape, row_tile
    n = w.shape[1] if transpose_rhs else w.shape[2]
    if m % tm or w.shape[2 if transpose_rhs else 1] != k:
        raise ValueError(f"xs {xs.shape} against w {w.shape} at {tm} rows "
                         "a tile")
    size = jnp.dtype(xs.dtype).itemsize
    # both slots, the pipeline's two tiles of rows in and out, the
    # product in float32 and its masked store
    vmem = (2 * k * n * size + 2 * tm * (k + n) * size + 3 * tm * n * 4
            + 4 * 2 ** 20)
    walk, steps = _walk(group_sizes.astype(jnp.int32), m, tm)

    def kernel(offsets, gids, tids, first, slot, nxt, x_ref, w_hbm, o_ref,
               w_buf, sems):
        i = pl.program_id(0)
        g, s = gids[i], slot[i]

        def matrix(group, into):
            return pltpu.make_async_copy(w_hbm.at[group], w_buf.at[into],
                                         sems.at[into])

        @pl.when(first[i] == 1)
        def _a_group_starts():
            @pl.when(i == 0)
            def _nobody_asked_for_the_first():
                matrix(g, s).start()

            matrix(g, s).wait()

            @pl.when(nxt[i] >= 0)
            def _the_next_has_this_groups_steps_to_arrive():
                matrix(nxt[i], 1 - s).start()

        if transpose_rhs:
            acc = lax.dot_general(x_ref[...], w_buf[s],
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        else:
            acc = jnp.dot(x_ref[...], w_buf[s],
                          preferred_element_type=jnp.float32)
        row = tids[i] * tm + lax.broadcasted_iota(jnp.int32, (tm, n), 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        # a tile is revisited by the groups that share it, one after
        # the other: each leaves the others' rows as they are
        o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)

    def row_tile_of(i, *walk):  # the step's tile of rows, in and out
        return walk[2][i], 0

    return pl.pallas_call(
        kernel,
        name="grouped_matmul_prefetch",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(steps,),
            in_specs=[pl.BlockSpec((tm, k), row_tile_of),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((tm, n), row_tile_of),
            scratch_shapes=[pltpu.VMEM((2,) + w.shape[1:], w.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(*walk, xs, w)


# (rows, K, N) tile of the matrices' gradient: the accumulator and the
# result's tile are `[K tile, N tile]` float32
TGMM_TILING = (512, 512, 1024)


def _rows_with_a_group(x, group_sizes):
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < jnp.sum(group_sizes), x, jnp.zeros_like(x))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_product(xs, w, group_sizes, row_tile: int = 128,
                    interpret: bool = False):
    """`gmm(xs, w.astype(xs.dtype), group_sizes)` that can be
    differentiated: `xs` [M, K] sorted by group, `w` [G, K, N] (the
    parameters' dtype; its gradient comes back in it), `group_sizes`
    [G].  Rows past the last group are zeros."""
    return _product_fwd(xs, w, group_sizes, row_tile, interpret)[0]


def _product_fwd(xs, w, group_sizes, row_tile, interpret):
    out = gmm(xs, w.astype(xs.dtype), group_sizes, row_tile=row_tile,
              interpret=interpret)
    return _rows_with_a_group(out, group_sizes), (xs, w, group_sizes)


def _product_bwd(row_tile, interpret, res, dy):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    xs, w, group_sizes = res
    dy = dy.astype(xs.dtype)
    dxs = gmm(dy, w.astype(xs.dtype), group_sizes, row_tile=row_tile,
              interpret=interpret, transpose_rhs=True)
    k, n = w.shape[1:]
    tm, tk, tn = TGMM_TILING
    dw = tgmm(xs.swapaxes(0, 1), dy, group_sizes,
              preferred_element_type=jnp.float32,
              tiling=(min(tm, xs.shape[0]), min(tk, k), min(tn, n)),
              interpret=interpret)
    return _rows_with_a_group(dxs, group_sizes), dw.astype(w.dtype), None


grouped_product.defvjp(_product_fwd, _product_bwd)
