"""Prefill attention of grouped queries as ONE Pallas TPU kernel: the
running softmax over key blocks with the scores left in VMEM.

Forward only.  `N` query rows, each `G` query heads a key/value head,
meet `S` key rows that lie contiguous: query row `i` is key row `lo +
i` (`lo` traced: a chunk of a long prompt behind its cached rows; 0: a
packed row of whole prompts), and sees key row `j` where

    kseg[j] == qseg[i]  and  j <= lo + i  and  qseg[i] >= 0

(`qseg` -1: padding; prompts packed end to end differ in segment).
Keys may be wider than values (192 / 128).  Operands in the caller's
dtype on the MXU with float32 accumulation; scores, max, sum and
accumulator float32; `p` cast to the operands' dtype before `p.v`: what
`models/mimo_v2._attend_blocks` computes in plain XLA, where one key
block's scores `[KV, G, N, block]` go to HBM once and come back twice.

THE WALK is the kernel's own loop, not the grid.  A grid step owns a
key/value head and a block of `block_q` query rows, all `G` heads of
the group at once (`[G * block_q, dk]` against a key block: the heads
share every key block that is fetched), and walks the key blocks
`[start, end)` that `block_walk` found for it BEFORE the call, from the
segments and `lo` (three scalars a query block, prefetched): a block
wholly above the diagonal, past the last real row's position or of
other prompts alone is neither fetched nor computed, the blocks `[start,
plain_end)` that every row of the query block sees whole take no mask,
the rest (the diagonal's, a prompt's edge) the exact mask.  A grid over
key blocks would pay a step for each block it skips (9 a query block
at an 8,720-row table, 1-2 of them live at `lo` 0).  K and V stay in HBM
and come block by block into two VMEM slots, the next block on its way
while this one folds.

On the v5e, 2,048 query rows x 4 x 16 heads, keys 192 / values 128,
bfloat16, behind `lo` = 0 / 2,048 / 4,096 / 6,144 rows: 1.76 / 3.04 /
4.28 / 5.60 ms a call at blocks of 128 x 1,024, its transposes
included (the XLA fold 4.81 / 9.29 / 13.8 / 18.3); a plain step of
2,048 score rows x 1,024 keys 9.9 us, 84% of the MXU at the padded key
width (keys of 192 lie in 256 lanes: one and a half passes cost two);
blocks of 128 x 512 take 2.09 / 4.30 / 6.51 / 8.71, the per-step
`[rows, 1]` max / sum / rescale no longer hidden (PERF.md section 6,
PR 53).

`interpret=True` is the caller's explicit choice (the CPU tests);
nothing here looks at the backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import _dot_f32

_NEG = -1e30
# a key row of no segment: padding the wrapper adds, equal to no query's
_NO_KEY = -2
_LANES = 128


def block_walk(qseg, kseg, lo, block_q: int, block_k: int):
    """Which key blocks each query block walks: `(start, plain_end,
    end)`, int32 `[N // block_q]` each.  Block `j` of `block_k` key rows
    is SKIPPED by query block `b` where no row of `b` can see a row of
    `j`: it lies before `start[b]` (every key of it belongs to a segment
    below the block's real rows') or from `end[b]` on (past the
    position of the block's last real row: above the diagonal, or past
    the context's end).  Blocks `[start, plain_end)` are seen WHOLE by
    every row (one segment on both sides, all real, at or below the
    block's first row's position) and take no mask.  Exact for any
    `qseg` / `kseg`: what is neither skipped nor plain is masked."""
    N, S = qseg.shape[0], kseg.shape[0]
    nq, nk = N // block_q, S // block_k
    qs, ks = qseg.reshape(nq, block_q), kseg.reshape(nk, block_k)
    real = qs >= 0
    row = jnp.arange(block_q, dtype=jnp.int32)
    first = lo + jnp.arange(nq, dtype=jnp.int32) * block_q
    last_real = jnp.max(jnp.where(real, row, -1), axis=1)
    end = jnp.where(last_real >= 0, (first + last_real) // block_k + 1, 0)
    end = jnp.minimum(end, nk)
    big = jnp.iinfo(jnp.int32).max
    qmin, qmax = qs.min(axis=1), qs.max(axis=1)
    qmin_real = jnp.min(jnp.where(real, qs, big), axis=1)
    kmin, kmax = ks.min(axis=1), ks.max(axis=1)
    blk = jnp.arange(nk, dtype=jnp.int32)[None, :]

    def run(cond):
        """How many leading blocks of each row of `cond` hold."""
        return jnp.sum(jnp.cumprod(cond.astype(jnp.int32), axis=1), axis=1)

    start = jnp.minimum(run(kmax[None, :] < qmin_real[:, None]), end)
    plain = (((qmin == qmax) & (qmin >= 0))[:, None]
             & ((kmin == kmax)[None, :] & (kmin[None, :] == qmin[:, None]))
             & ((blk + 1) * block_k - 1 <= first[:, None]))
    # the run of plain blocks from `start` on
    plain_end = jnp.clip(run(plain | (blk < start[:, None])), start, end)
    return tuple(x.astype(jnp.int32) for x in (start, plain_end, end))


@functools.lru_cache(maxsize=32)
def _build(KV, G, N, S, dk, dv, TQ, TK, dtype, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dt = jnp.dtype(dtype)
    nq, nk = N // TQ, S // TK
    R = G * TQ  # rows of a grid step's score tile

    def kernel(lo_ref, start_ref, plain_ref, end_ref, q_ref, qseg_ref,
               kseg_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_ref,
               l_ref, acc_ref):
        h, b = pl.program_id(0), pl.program_id(1)
        start, plain_end, end = start_ref[b], plain_ref[b], end_ref[b]

        def copies(j, slot):
            rows = pl.ds(pl.multiple_of(j * TK, TK), TK)
            return (pltpu.make_async_copy(k_hbm.at[h, rows], k_buf.at[slot],
                                          sems.at[0, slot]),
                    pltpu.make_async_copy(v_hbm.at[h, rows], v_buf.at[slot],
                                          sems.at[1, slot]))

        @pl.when(start < end)
        def _first():
            for c in copies(start, 0):
                c.start()

        m_ref[...] = jnp.full((R, 1), _NEG, jnp.float32)
        l_ref[...] = jnp.zeros((R, 1), jnp.float32)
        acc_ref[...] = jnp.zeros((R, dv), jnp.float32)
        q = q_ref[0].reshape(R, dk)

        def fold(j, masked):
            slot = (j - start) % 2
            for c in copies(j, slot):
                c.wait()

            @pl.when(j + 1 < end)
            def _next():
                for c in copies(j + 1, 1 - slot):
                    c.start()

            s = _dot_f32(q, k_buf[slot], trans_b=True) * scale   # [R, TK]
            m = m_ref[...]
            if masked:
                qpos = lo_ref[0] + b * TQ + lax.broadcasted_iota(
                    jnp.int32, (TQ, TK), 0)
                kpos = j * TK + lax.broadcasted_iota(jnp.int32, (TQ, TK), 1)
                mask = ((kseg_ref[pl.ds(j, 1), :] == qseg_ref[...])
                        & (kpos <= qpos))[None]                  # [1, TQ, TK]
                s = jnp.where(mask, s.reshape(G, TQ, TK), _NEG).reshape(R, TK)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                # a row that has seen no key yet has m_new = _NEG and
                # p = 1 in every masked column: weigh them out
                p = jnp.where(mask, p.reshape(G, TQ, TK), 0.0).reshape(R, TK)
            corr = jnp.exp(m - m_new)
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + _dot_f32(p.astype(dt),
                                                          v_buf[slot])
            m_ref[...] = m_new

        lax.fori_loop(start, plain_end, lambda j, _: fold(j, False), None)
        lax.fori_loop(plain_end, end, lambda j, _: fold(j, True), None)
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = o.reshape(G, TQ, dv).astype(o_ref.dtype)

    tile = R * TK * 4
    vmem = (4 * tile                                  # s, p, their kin
            + 2 * 2 * R * (dk + dv) * dt.itemsize     # q and o, pipelined
            + 2 * TK * (dk + dv) * dt.itemsize        # the two K / V slots
            + R * (dv + 2 * 128) * 4                  # acc, m, l
            + (8 << 20))
    return pl.pallas_call(
        kernel,
        name="prefill_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(KV, nq),
            in_specs=[
                pl.BlockSpec((1, G, TQ, dk), lambda h, b, *_: (h, 0, b, 0)),
                pl.BlockSpec((TQ, 1), lambda h, b, *_: (b, 0)),
                pl.BlockSpec((nk, TK), lambda h, b, *_: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec((1, G, TQ, dv),
                                   lambda h, b, *_: (h, 0, b, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, TK, dk), dt),
                pltpu.VMEM((2, TK, dv), dt),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((KV, G, N, dv), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem,
        ),
        interpret=interpret,
    )


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def prefill_attention(q, k, v, qseg, kseg, lo, *, scale: float,
                      block_q: int = 128, block_k: int = 1024,
                      interpret: bool = False):
    """q `[N, KV, G, dk]`, k `[S, KV, dk]`, v `[S, KV, dv]`, `qseg` [N]
    and `kseg` [S] int32 segments (a query's -1: padding), `lo` (traced)
    the key row of query row 0 -> `[N, KV, G, dv]` in q's dtype; a row
    that sees no key comes back zero.  Rows are padded to whole blocks
    here (`block_q` / `block_k`, cut to the rows there are)."""
    N, KV, G, dk = q.shape
    S, dv = k.shape[0], v.shape[-1]
    TQ = min(block_q, _round_up(N, 8))
    TK = min(block_k, _round_up(S, 8))
    Np, Sp = _round_up(N, TQ), _round_up(S, TK)
    qseg = jnp.pad(qseg.astype(jnp.int32), (0, Np - N), constant_values=-1)
    kseg = kseg.astype(jnp.int32)
    kseg = jnp.pad(jnp.where(kseg < 0, _NO_KEY, kseg), (0, Sp - S),
                   constant_values=_NO_KEY)
    lo = jnp.asarray(lo, jnp.int32)
    walk = block_walk(qseg, kseg, lo, TQ, TK)
    # whole lane tiles: a head of 192 lies in 256 lanes in HBM anyway,
    # and a zero column adds nothing to a product
    dkp, dvp = _round_up(dk, _LANES), _round_up(dv, _LANES)

    def fit(x, rows, width):
        """`x` [rows', ..., width'] zero-padded to `rows` x `width`."""
        return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 2)
                       + ((0, width - x.shape[-1]),))

    call = _build(KV, G, Np, Sp, dkp, dvp, TQ, TK, jnp.dtype(q.dtype).name,
                  float(scale), bool(interpret))
    o = call(lo.reshape(1), *walk,
             jnp.transpose(fit(q, Np, dkp), (1, 2, 0, 3)), qseg[:, None],
             kseg.reshape(Sp // TK, TK),
             jnp.swapaxes(fit(k, Sp, dkp), 0, 1),
             jnp.swapaxes(fit(v, Sp, dvp), 0, 1))
    return jnp.transpose(o, (2, 0, 1, 3))[:N, ..., :dv]
