"""Flash attention as Pallas TPU kernels, forward AND backward.

Reference has no TPU kernels (its hot ops ride CUDA/cuDNN through
torch); this is the TPU-native equivalent of its fused-attention path.
Design per /opt/skills/guides/pallas_guide.md: q blocks stay resident in
VMEM while the kv sequence streams block-by-block through an online
softmax (running max / sum / accumulator in f32), so the [Tq, Tk] score
matrix never materializes in HBM — the memory shape that unlocks long
context on one chip.

The GRID is coarse and the walk is INSIDE the grid step.  A grid step
owns a `[block_q, block_k]` tile, by default 1024 x 1024: at T 1024 (the
train shapes) that is ONE tile a (batch, head), grid `(B*H, 1, 1)`.
Finer grid blocks lost when measured (512 x 512: 78.9k against 89.7k
tokens/s at GPT-2 124M; a grid step costs ~0.35 us and fetches K and V
again), and a single tile can skip nothing at the grid level: until
PR 32 both kernels computed all T x T scores of a head and masked half
of them away.  Now the tile is cut into sub-tiles (`_sub_tile`: 256
wide where that cuts the block at least in two) and walked with static
bounds (`_row_walk`, `_col_walk`; `causal_walk` counts them): sub-tiles
wholly above the diagonal are never computed, the iota / compare /
select of the mask falls only on the sub-tiles the diagonal crosses,
and everything below it is plain.  At T 1024 that is 10 of 16 sub-tiles
visited, 4 masked.  The sub-tiles a strip holds are taken TOGETHER: a
row-group's max, exp, sum and its two products cost a fixed amount a
strip whatever its width, and on the v5e a walk sub-tile by sub-tile
(rolled loops, an online-softmax update each) ran SLOWER than no
skipping at all (PERF.md section 6, PR 32).

- forward (`_build_fwd`): q sub-block by q sub-block, each against its
  live columns `[0, vis * sub_k)` as one strip: one score product, the
  mask on the strip's last sub-tile(s), one max / exp / sum a row, one
  value product.  Where one grid step sees the whole kv sequence the
  softmax is complete in the strip and no running state is kept; across
  kv grid steps the running max / sum / f32 accumulator live in VMEM
  scratch `[block_q, 1]` / `[block_q, D]` (keepdims columns: the old
  forward kept 1-D vectors and relaid them out with `[:, None]` every
  step, and the same whole-tile math on columns ran 1.8 x faster).  A grid tile wholly below the diagonal takes no
  mask, one above it is skipped, a diagonal tile of square blocks is
  walked like the single tile; only rectangular blocks, whose offset
  from the diagonal is not static, mask a whole tile.
- fused backward (`_build_bwd_fused`, block_q == block_k == T: the
  train shapes): ONE kernel a (batch, head) computes s, p = exp(s -
  lse), dp, ds once each per live element and gives dQ, dK, dV — the
  split pair pays 7 matmuls + 2 exps + an XLA delta pass for the same
  math.  It walks kv sub-block by kv sub-block: the q rows the diagonal
  crosses under the mask, every row below them as one strip; dK_j and
  dV_j are summed in f32 values over the two, dQ gathers in an f32
  `[T, D]` VMEM scratch for the whole head (256 KB at hd 64), and
  `delta = rowsum(do * out)` is computed in the kernel, once a head,
  into a `[T, 1]` scratch.
- split backward (T > block): the FlashAttention-2 pair with the
  per-row logsumexp saved from forward, which is the grouped pair below
  with a group of ONE query head in the rows (causal or not): one pair
  of kernels for every call past the fused kernel's limit.  The cell at
  8,192 tokens measures it with grouped heads; no cell runs it with
  equal heads.
- grouped heads and a window (`_build_fwd_grouped`,
  `_build_bwd_dq_grouped`, `_build_bwd_dkv_grouped`: K and V with fewer
  heads than Q, and / or `window`; causal only, but for the backward
  pair at equal heads): the `G` query heads of
  a KV head ride in the ROWS of one `[G * block_q, D]` operand, so a KV
  head's rows are fetched once a group and dK / dV's sum over the group
  is the product's own contraction; the kv axis of the grid is as long
  as the tiles one q block can see under the window, tiles all of whose
  pairs are live take no mask, the diagonal and the window's lower edge
  are masked; `delta` is taken in the kernels from the tile's own rows
  of `do` and `out`, and the forward gives the rows' log-sum-exp as
  ROWS of lanes.  Under the fused backward's limit the fused kernel
  runs a query head (`_strips`), its dK and dV summed over the group
  outside.  A call with equal heads and no window takes the forward
  and the fused backward above, text for text, and of this only the
  backward pair past the fused kernel's limit.
- matmuls run on the MXU in the input dtype with f32 accumulation
  (`preferred_element_type`); max, exp, sum, lse and every accumulator
  are f32; `p` and `ds` are cast to the input dtype for their products.
  A power-of-two softmax scale (hd 16, 64, 256) is folded into a bf16
  operand, exactly; any other stays on the f32 scores.
- `dimension_semantics`: batch*heads and q blocks are parallel grid
  axes, the kv walk is the sole sequential axis.

With hd 64 every product half-fills the MXU (the contraction of q.k^T,
the output columns of p.v), so at hd 64 and hd 128 a head costs the
same MXU time; the roofline in `benchmarks/roofline.py` counts useful
FLOPs, so a 256-wide walk at the MXU's own limit would read ~40% there
at hd 64 (half the pairs needed of 62.5% computed, on half the array).

The kernels are what runs: a shape they cannot tile raises, naming the
shape, and nothing here looks at the backend or gives way to the XLA
path.  `interpret=True` is the caller's explicit choice (the CPU
tests); `tests/test_aot_tpu_compile.py` lowers the train shapes for a
described v5e chip.  The benchmark finds the calls in a trace by their
RESULT shapes (`(bf16[B*H,T,hd], f32[B*H,T,1])` and three
`bf16[B*H,T,hd]`): operands and scratch are free, results are not.

Under remat the forward kernel is NOT replayed.  `_fwd` names its two
results (`FLASH_RESIDUALS`: "flash_out", and "flash_lse" squeezed to
`[B*H, T]`) with `checkpoint_name`, and `checkpoint_block` is the
`jax.checkpoint` that keeps exactly those: a block's replay recomputes
q, k, v and everything else, and reads the kernel's results back (1 MB
+ 32 MB of numbers a layer at the train shapes).  That buys a fifth of
the call it spares (0.15 of 0.65 ms), because the kernel's lse is
`f32[B*H, T, 1]`, lane-padded 128 x in HBM: squeezing it after the
forward call and handing it back to the backward kernel are two XLA
passes over 134 MB (0.18 + 0.30 ms) that the kernels hide under their
products (PERF.md section 6, PR 46).  Outside such a checkpoint the
names lower to nothing.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_NEG_INF = -1e30

# what a remat block keeps of the forward kernel (`checkpoint_block`)
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _dot_f32(a, b, trans_b=False):
    """MXU matmul: any-dtype in, f32 accumulate/out."""
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# the walk: which sub-tiles of a causal tile are live, and which of
# those the diagonal crosses
# ----------------------------------------------------------------------
def _sub_tile(block: int) -> int:
    """The sub-tile width a block of `block` rows (or columns) is
    walked in: 256 where that cuts it at least in two, else 128, else
    the block whole.  256 is what the v5e measured fastest for the
    forward and the fused backward at hd 64 and hd 128, bf16 (PERF.md
    section 6, PR 32): 128 skips more (36 of 64 sub-tiles at T 1024
    against 10 of 16) and loses it again in per-strip costs, 512 skips
    only one tile of four."""
    for sub in (256, 128):
        if block % sub == 0 and block > sub:
            return sub
    return block


def _clip(x, lo, hi):
    return max(lo, min(x, hi))


def _row_walk(r0, sub_q, sub_k, n):
    """For the q sub-block on rows `[r0, r0 + sub_q)` and `n` kv
    sub-blocks on columns `j * sub_k`: `(full, vis)`.  Sub-blocks
    `[0, full)` lie at or below the diagonal in every row (no mask),
    `[full, vis)` are crossed by it (masked), `[vis, n)` lie wholly
    above it (skipped).  Python ints: the walk is static."""
    full = _clip(r0 + 1, 0, n * sub_k) // sub_k
    vis = -(-_clip(r0 + sub_q, 0, n * sub_k) // sub_k)
    return full, vis


def _col_walk(c0, sub_k, sub_q, n):
    """The backward's view of the same walk, for the kv sub-block on
    columns `[c0, c0 + sub_k)` and `n` q sub-blocks on rows
    `i * sub_q`: `(vis, full)`.  Sub-blocks `[0, vis)` see none of
    these columns (skipped), `[vis, full)` are crossed by the diagonal
    (masked), `[full, n)` see all of them (no mask)."""
    vis = _clip(c0, 0, n * sub_q) // sub_q
    full = -(-_clip(c0 + sub_k - 1, 0, n * sub_q) // sub_q)
    return vis, full


def causal_walk(block_q, block_k, sub_q, sub_k, q0=0, k0=0):
    """Sub-tiles the kernels visit, and mask, in the `[block_q,
    block_k]` tile whose first row is `q0` and first column `k0`, of
    `total`.  Counted from `_row_walk`, where the forward takes its
    strips; the fused backward takes the same walk kv sub-block by kv
    sub-block from `_strips`, which without a window is `_col_walk`'s
    (`tests/test_ops.py` holds the two views to the same counts,
    `tests/test_flash_remat.py` `_strips` to `_col_walk`)."""
    n_q, n_k = block_q // sub_q, block_k // sub_k
    rows = [_row_walk(q0 - k0 + i * sub_q, sub_q, sub_k, n_k)
            for i in range(n_q)]
    return {"visited": sum(vis for _, vis in rows),
            "masked": sum(vis - full for full, vis in rows),
            "total": n_q * n_k}


def _causal_mask(s, r0, c0):
    """`s` is the tile whose first row is `r0` and first column `c0`."""
    below = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    return jnp.where(below >= c0 - r0, s, _NEG_INF)


def _window_mask(s, r0, c0, window):
    """`_causal_mask` with a lower edge: row `i` keeps the columns
    `i - window < j <= i`."""
    below = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)) + (r0 - c0)
    return jnp.where((below >= 0) & (below < window), s, _NEG_INF)


def _masked(s, r0, c0, window=None):
    return (_causal_mask(s, r0, c0) if window is None
            else _window_mask(s, r0, c0, window))


def _strips(c0, sub, n, window):
    """The fused backward's walk with a window: for the kv sub-block on
    columns `[c0, c0 + sub)` the q rows that see any of them, as
    `(first row, last row, masked)` strips of whole sub-blocks.  A
    sub-block of rows `[r, r + sub)` sees none of the columns where it
    lies above the diagonal or wholly past the window, all of them
    (no mask) where `c0 + sub - 1 <= r` and `r + sub - 1 < c0 +
    window`.  Neighbours of one kind are one strip.  `window` None is
    `_col_walk`: the crossed sub-blocks, then all below as one strip."""
    out = []
    for i in range(n):
        r = i * sub
        if r + sub - 1 < c0 or (window is not None
                                and r - window >= c0 + sub - 1):
            continue
        plain = c0 + sub - 1 <= r and (window is None
                                       or r + sub - 1 < c0 + window)
        if out and out[-1][2] == (not plain) and out[-1][1] == r:
            out[-1] = (out[-1][0], r + sub, not plain)
        else:
            out.append((r, r + sub, not plain))
    return out


def _folds(scale):
    """A power-of-two scale (hd 16, 64, 256) multiplies a bf16 operand
    exactly, so it is applied to `[rows, hd]` once instead of to every
    `[rows, cols]` of scores; any other scale stays on the f32 scores."""
    return math.frexp(scale)[0] == 0.5


def _build_fwd(causal, scale, block_q, block_k, n_k, interpret, dtype, sub):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sub_q, sub_k = sub
    n_sq, n_sk = block_q // sub_q, block_k // sub_k
    fold = _folds(scale)

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *state):
        # `state`: running max, sum and accumulator of the block's rows
        # across the kv grid steps; none where one step sees every kv
        m_ref, l_ref, acc_ref = state or (None, None, None)
        q0 = pl.program_id(1) * block_q
        k0 = pl.program_id(2) * block_k

        if state:
            @pl.when(pl.program_id(2) == 0)
            def _init():
                m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
                l_ref[...] = jnp.zeros_like(l_ref)
                acc_ref[...] = jnp.zeros_like(acc_ref)

        def walk(rel):
            """The tile whose first row lies `rel` below its first
            column.  A Python int, so every bound is static: a q
            sub-block meets its live columns `[0, vis * sub_k)` as ONE
            strip (one product, one max, one exp, one sum a row), the
            mask falls on the sub-tiles `[full, vis)` the diagonal
            crosses, and the strips are straight-line code the
            scheduler overlaps.  None: not known until run time
            (rectangular blocks), the whole width then goes under the
            mask."""
            for i in range(n_sq):
                r = i * sub_q
                rows = slice(r, r + sub_q)
                if rel is None:
                    full, vis, origin = 0, n_sk, (q0 + r, k0)
                else:
                    full, vis = _row_walk(rel + r, sub_q, sub_k, n_sk)
                    origin = (rel + r, full * sub_k)
                lo, hi = full * sub_k, vis * sub_k
                qb = q_ref[rows, :]  # [sub_q, D] compute dtype
                s = _dot_f32(qb * scale if fold else qb, k_ref[:hi, :],
                             trans_b=True)
                if not fold:
                    s = s * scale
                if hi > lo:
                    crossed = _causal_mask(s[:, lo:], *origin)
                    s = crossed if lo == 0 else jnp.concatenate(
                        [s[:, :lo], crossed], axis=1)
                m = jnp.max(s, axis=-1, keepdims=True)
                if state:
                    m_prev = m_ref[rows, :]
                    m = jnp.maximum(m_prev, m)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                acc = _dot_f32(p.astype(dtype), v_ref[:hi, :])
                if state:
                    corr = jnp.exp(m_prev - m)
                    m_ref[rows, :] = m
                    l_ref[rows, :] = l_ref[rows, :] * corr + l
                    acc_ref[rows, :] = acc_ref[rows, :] * corr + acc
                else:  # a row's max weighs exp(0): l >= 1
                    o_ref[rows, :] = (acc / l).astype(o_ref.dtype)
                    lse_ref[rows, :] = m + jnp.log(l)

        if not causal:
            walk(block_k)
        elif n_k == 1:
            # the whole sequence in one tile (the train shapes), or
            # every q block against all of kv
            walk(0 if block_q == block_k else None)
        else:
            _when_live(
                q0, block_q, k0, block_k, lambda: walk(block_k),
                lambda: walk(0 if block_q == block_k else None))

        if state:
            @pl.when(pl.program_id(2) == n_k - 1)
            def _finalize():
                l = l_ref[...]
                # fully-masked rows (can't happen causally, but keep the
                # kernel total): lse=-inf, out=0
                safe_l = jnp.where(l == 0.0, 1.0, l)
                o_ref[...] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
                # lse rides a trailing singleton lane dim: TPU block
                # specs need the last two dims (8, 128)-divisible or
                # array-equal
                lse_ref[...] = m_ref[...] + jnp.log(safe_l)

    def call(q, k, v):
        BH, T, D = q.shape
        n_q = T // block_q
        grid = (BH, n_q, n_k)
        return pl.pallas_call(
            kernel,
            name="flash_fwd",
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, T, D), q.dtype),
                jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ] if n_k > 1 else [],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(q, k, v)

    return call


def _bwd_tile(qb, kb, vb, dob, lse, delta, scale, mask):
    """One `[rows, cols]` tile of the backward: `p = exp(s - lse)` and
    `ds = p * (dp - delta)`, each computed once.  `scale` is None where
    the caller folded it into `kb`; `mask` is None or the tile's
    `(first row, first column)`."""
    s = _dot_f32(qb, kb, trans_b=True)
    if scale is not None:
        s = s * scale
    if mask is not None:
        s = _masked(s, *mask)
    p = jnp.exp(s - lse)  # [rows, cols] - [rows, 1] broadcast
    ds = p * (_dot_f32(dob, vb, trans_b=True) - delta)
    if scale is not None:
        ds = ds * scale
    return p, ds


def _when_live(q0, block_q, k0, block_k, below, crossing):
    """A causal grid tile: `below()` where it lies wholly at or below
    the diagonal (no mask needed), `crossing()` where the diagonal
    crosses it, nothing where it lies above."""
    from jax.experimental import pallas as pl

    is_below = k0 + block_k - 1 <= q0
    pl.when(is_below)(below)
    pl.when(jnp.logical_and(jnp.logical_not(is_below),
                            k0 <= q0 + block_q - 1))(crossing)


def _build_bwd_fused(causal, scale, T, interpret, dtype, sub,
                     window=None, group=1):
    """Backward for the whole-sequence tile (block_q == block_k == T):
    with a (BH,) grid there is no cross-step accumulation, so dq/dk/dv
    come out of ONE kernel (the module docstring says how it walks).
    `window`: the strips of `_strips` in place of the causal pair.
    `group` > 1: query head `b` reads K and V of kv head `b // group`
    and gives ITS dK and dV, which the caller sums over the group."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_sub = T // sub
    fold = _folds(scale)

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, out_ref,
               dq_ref, dk_ref, dv_ref, dq_acc, dlt_ref):
        dlt_ref[...] = jnp.sum(
            do_ref[...].astype(jnp.float32) * out_ref[...].astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        dq_acc[...] = jnp.zeros_like(dq_acc)

        for j in range(n_sub):
            c = j * sub
            cols = slice(c, c + sub)
            kb, vb = k_ref[cols, :], v_ref[cols, :]
            # folded: s, dq and (at the end) dk take the scale through
            # an operand, exactly; `ds` below is then unscaled
            ks = kb * scale if fold else kb
            dk = dv = jnp.zeros((sub, q_ref.shape[-1]), jnp.float32)
            # the q sub-block the diagonal crosses, under the mask, then
            # every one below it as ONE strip; under a window the strip
            # ends where the window's lower edge crosses (`_strips`)
            for lo, hi, masked in (_strips(c, sub, n_sub, window) if causal
                                   else [(0, T, False)]):
                rows = slice(lo, hi)
                qb, dob = q_ref[rows, :], do_ref[rows, :]
                p, ds = _bwd_tile(
                    qb, ks, vb, dob, lse_ref[rows, :], dlt_ref[rows, :],
                    None if fold else scale,
                    (lo, c, window) if masked else None)
                ds = ds.astype(dtype)
                dq_acc[rows, :] += _dot_f32(ds, ks)
                dk = dk + _dot_f32(ds.T, qb)
                dv = dv + _dot_f32(p.astype(dtype).T, dob)
            if fold:
                dk = dk * scale
            dk_ref[cols, :] = dk.astype(dk_ref.dtype)
            dv_ref[cols, :] = dv.astype(dv_ref.dtype)
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    def call(q, k, v, do, lse, out):
        BH, T_, D = q.shape
        spec = pl.BlockSpec((None, T_, D), lambda b: (b, 0, 0))
        vec = pl.BlockSpec((None, T_, 1), lambda b: (b, 0, 0))
        kv = spec if group == 1 else pl.BlockSpec(
            (None, T_, D), lambda b: (b // group, 0, 0))
        return pl.pallas_call(
            kernel,
            name="flash_bwd_fused",
            grid=(BH,),
            in_specs=[spec, kv, kv, spec, vec, spec],
            out_specs=[spec, spec, spec],
            out_shape=[jax.ShapeDtypeStruct((BH, T_, D), q.dtype)] * 3,
            scratch_shapes=[
                pltpu.VMEM((T_, D), jnp.float32),
                pltpu.VMEM((T_, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
            ),
            interpret=interpret,
        )(q, k, v, do, lse, out)

    return call


# ----------------------------------------------------------------------
# grouped heads and a window: a KV head's rows are read once for the
# `G` query heads that share it, and only the tiles a window leaves
# are walked
# ----------------------------------------------------------------------
# The q operand is `[B * KV, G, T, D]` and a grid step holds the `G`
# heads' rows of one q block as ONE `[G * block_q, D]` operand against
# one `[block_k, D]` tile of the KV head: the group rides in the
# product's rows, so K and V are fetched once a group, and in the dK /
# dV kernel the sum over the group is the product's own contraction.
# The kv axis of the grid is as long as the tiles ONE q block can see
# (`_kv_span`): a q block starts at its first live tile, a step past
# its last one computes nothing and, its block index held at the last,
# fetches nothing.  A tile all of whose pairs are live takes no mask;
# the two edges (the diagonal, and the window's lower edge `j > i -
# window`) are masked.
_GROUP_ROWS = 2048       # rows (G * block_q) a forward step holds
_GROUP_ROWS_BWD = 1024   # the backward keeps four [rows, block_k] f32
_GROUP_BLOCK_K = 512
_GROUP_VMEM_BYTES = 64 * 2 ** 20


def grouped_blocks(T: int, group: int, block_q: int, block_k: int,
                   rows: int = _GROUP_ROWS):
    """(block_q, block_k) of the grouped kernels: the caller's, held to
    `rows` rows a step for the whole group and `_GROUP_BLOCK_K` columns,
    halved until they divide T."""
    bq = max(min(block_q, T, max(rows // group, 8)), 1)
    bk = max(min(block_k, T, _GROUP_BLOCK_K), 1)
    while T % bq:
        bq //= 2
    while T % bk:
        bk //= 2
    return bq, bk


def _kv_span(i, block_q, block_k, window, n_k, causal=True):
    """First and last kv tile the q block `i` sees (traced or int)."""
    if not causal:
        return 0, n_k - 1
    q0 = i * block_q
    first = 0 if window is None else (
        jnp.maximum(q0 - window + 1, 0) // block_k)
    return first, (q0 + block_q - 1) // block_k


def _q_span(j, block_q, block_k, window, n_q, causal=True):
    """First and last q tile that sees the kv block `j`."""
    if not causal:
        return 0, n_q - 1
    k0 = j * block_k
    last = n_q - 1 if window is None else jnp.minimum(
        (k0 + block_k + window - 2) // block_q, n_q - 1)
    return k0 // block_q, last


def span_steps(T, block_q, block_k, window):
    """(kv steps a q block walks, q steps a kv block walks): the
    longest span of any block, static."""
    n_q, n_k = T // block_q, T // block_k
    if window is None:
        return n_k, n_q
    kv = max((i * block_q + block_q - 1) // block_k
             - max(i * block_q - window + 1, 0) // block_k + 1
             for i in range(n_q))
    qs = max(min((j * block_k + block_k + window - 2) // block_q, n_q - 1)
             - (j * block_k) // block_q + 1 for j in range(n_k))
    return kv, qs


def _tile_plain(q0, block_q, k0, block_k, window, causal=True):
    """Every pair of the tile is live: no mask."""
    if not causal:
        return True
    plain = k0 + block_k - 1 <= q0
    if window is not None:
        plain = jnp.logical_and(plain, k0 > q0 + block_q - 1 - window)
    return plain


def _group_rows(ref, rows):
    """`[G, block, D]` -> `[G * block, D]`: the group in the rows."""
    x = ref[...]
    return x.reshape(rows, x.shape[-1])


def _group_mask(s, q0, k0, block_q, window):
    """The tile's mask where row `r` of `[G * block_q, block_k]` is
    query `q0 + r % block_q`."""
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % block_q
    below = row - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + (q0 - k0)
    live = below >= 0
    if window is not None:
        live = live & (below < window)
    return jnp.where(live, s, _NEG_INF)


def _grouped_params(pltpu, semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_GROUP_VMEM_BYTES)


def _build_fwd_grouped(scale, T, G, block_q, block_k, window, interpret,
                       dtype):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    steps, _ = span_steps(T, block_q, block_k, window)
    n_k = T // block_k
    R = G * block_q

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref):
        i, jj = pl.program_id(1), pl.program_id(2)
        q0 = i * block_q
        first, last = _kv_span(i, block_q, block_k, window, n_k)
        j = first + jj
        k0 = j * block_k

        @pl.when(jj == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def tile(masked):
            s = _dot_f32(_group_rows(q_ref, R), k_ref[...],
                         trans_b=True) * scale
            if masked:
                s = _group_mask(s, q0, k0, block_q, window)
            m_prev = m_ref[...]
            m = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m)
            corr = jnp.exp(m_prev - m)
            m_ref[...] = m
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + _dot_f32(
                p.astype(dtype), v_ref[...])

        plain = _tile_plain(q0, block_q, k0, block_k, window)
        live = j <= last
        pl.when(jnp.logical_and(live, plain))(lambda: tile(False))
        pl.when(jnp.logical_and(live, jnp.logical_not(plain)))(
            lambda: tile(True))

        @pl.when(jj == steps - 1)
        def _finalize():
            # a row's own column is always live: l >= exp(0) once the
            # row's maximum has been seen
            l = l_ref[...]
            o_ref[...] = (acc_ref[...] / l).reshape(o_ref.shape).astype(
                o_ref.dtype)
            # the rows' log-sum-exp leaves as ONE ROW of R lanes: a
            # `[R, 1]` result is lane-padded 128 x in HBM (256 MB a
            # layer at 2 x 8,192 x 32), and a remat block would keep
            # that buffer.  Column -> row through the transpose unit,
            # once a q block
            lse = m_ref[...] + jnp.log(l)
            lse_ref[...] = jnp.broadcast_to(lse, (R, 128)).T[:1, :]

    def kv_tile(b, i, jj):
        first, last = _kv_span(i, block_q, block_k, window, n_k)
        return b, jnp.minimum(first + jj, last), 0

    def call(q, k, v):
        BKV, _, _, D = q.shape
        rows = pl.BlockSpec((None, G, block_q, D),
                            lambda b, i, jj: (b, 0, i, 0))
        return pl.pallas_call(
            kernel,
            name="flash_fwd_grouped",
            grid=(BKV, T // block_q, steps),
            in_specs=[rows,
                      pl.BlockSpec((None, block_k, D), kv_tile),
                      pl.BlockSpec((None, block_k, D), kv_tile)],
            out_specs=[rows,
                       pl.BlockSpec((None, None, 1, R),
                                    lambda b, i, jj: (b, i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((BKV, G, T, D), q.dtype),
                       jax.ShapeDtypeStruct((BKV, T // block_q, 1, R),
                                            jnp.float32)],
            scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, D), jnp.float32)],
            compiler_params=_grouped_params(
                pltpu, ("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v)

    return call


def _grouped_bwd_tile(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, R,
                      scale, mask):
    """`(q, do, p, ds)` of one tile, the group in the rows; `delta =
    rowsum(do * out)` is taken here from the tile's own rows.  `mask`:
    None or `_group_mask`'s `(q0, k0, block_q, window)`."""
    qb, dob = _group_rows(q_ref, R), _group_rows(do_ref, R)
    delta = jnp.sum(dob.astype(jnp.float32)
                    * _group_rows(o_ref, R).astype(jnp.float32),
                    axis=-1, keepdims=True)
    s = _dot_f32(qb, k_ref[...], trans_b=True) * scale
    if mask is not None:
        s = _group_mask(s, *mask)
    p = jnp.exp(s - _group_rows(lse_ref, R))
    ds = p * (_dot_f32(dob, v_ref[...], trans_b=True) - delta) * scale
    return qb, dob, p, ds


def _build_bwd_dq_grouped(scale, T, G, block_q, block_k, window, interpret,
                          dtype, causal=True):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    steps, _ = span_steps(T, block_q, block_k, window)
    n_k = T // block_k
    R = G * block_q

    def kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, acc_ref):
        i, jj = pl.program_id(1), pl.program_id(2)
        q0 = i * block_q
        first, last = _kv_span(i, block_q, block_k, window, n_k, causal)
        j = first + jj
        k0 = j * block_k

        @pl.when(jj == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def tile(masked):
            _, _, _, ds = _grouped_bwd_tile(
                q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, R, scale,
                (q0, k0, block_q, window) if masked else None)
            acc_ref[...] += _dot_f32(ds.astype(dtype), k_ref[...])

        plain = _tile_plain(q0, block_q, k0, block_k, window, causal)
        live = j <= last
        pl.when(jnp.logical_and(live, plain))(lambda: tile(False))
        pl.when(jnp.logical_and(live, jnp.logical_not(plain)))(
            lambda: tile(True))

        @pl.when(jj == steps - 1)
        def _fin():
            dq_ref[...] = acc_ref[...].reshape(dq_ref.shape).astype(
                dq_ref.dtype)

    def kv_tile(b, i, jj):
        first, last = _kv_span(i, block_q, block_k, window, n_k, causal)
        return b, jnp.minimum(first + jj, last), 0

    def call(q, k, v, do, out, lse):
        BKV, _, _, D = q.shape
        rows = pl.BlockSpec((None, G, block_q, D),
                            lambda b, i, jj: (b, 0, i, 0))
        vec = pl.BlockSpec((None, G, block_q, 1),
                           lambda b, i, jj: (b, 0, i, 0))
        kv = pl.BlockSpec((None, block_k, D), kv_tile)
        return pl.pallas_call(
            kernel,
            name="flash_bwd_dq_grouped",
            grid=(BKV, T // block_q, steps),
            in_specs=[rows, kv, kv, rows, rows, vec],
            out_specs=rows,
            out_shape=jax.ShapeDtypeStruct((BKV, G, T, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((R, D), jnp.float32)],
            compiler_params=_grouped_params(
                pltpu, ("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, out, lse)

    return call


def _build_bwd_dkv_grouped(scale, T, G, block_q, block_k, window, interpret,
                           dtype, causal=True):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, steps = span_steps(T, block_q, block_k, window)
    n_q = T // block_q
    R = G * block_q

    def kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref,
               dk_acc, dv_acc):
        j, ii = pl.program_id(1), pl.program_id(2)
        k0 = j * block_k
        first, last = _q_span(j, block_q, block_k, window, n_q, causal)
        i = first + ii
        q0 = i * block_q

        @pl.when(ii == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        def tile(masked):
            qb, dob, p, ds = _grouped_bwd_tile(
                q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, R, scale,
                (q0, k0, block_q, window) if masked else None)
            # the G heads' rows contract together: the group's sum
            dv_acc[...] += _dot_f32(p.astype(dtype).T, dob)
            dk_acc[...] += _dot_f32(ds.astype(dtype).T, qb)

        plain = _tile_plain(q0, block_q, k0, block_k, window, causal)
        live = i <= last
        pl.when(jnp.logical_and(live, plain))(lambda: tile(False))
        pl.when(jnp.logical_and(live, jnp.logical_not(plain)))(
            lambda: tile(True))

        @pl.when(ii == steps - 1)
        def _fin():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    def q_tile(b, j, ii):
        first, last = _q_span(j, block_q, block_k, window, n_q, causal)
        return b, 0, jnp.minimum(first + ii, last), 0

    def call(q, k, v, do, out, lse):
        BKV, _, _, D = q.shape
        rows = pl.BlockSpec((None, G, block_q, D), q_tile)
        vec = pl.BlockSpec((None, G, block_q, 1), q_tile)
        kv = pl.BlockSpec((None, block_k, D), lambda b, j, ii: (b, j, 0))
        return pl.pallas_call(
            kernel,
            name="flash_bwd_dkv_grouped",
            grid=(BKV, T // block_k, steps),
            in_specs=[rows, kv, kv, rows, rows, vec],
            out_specs=[kv, kv],
            out_shape=[jax.ShapeDtypeStruct((BKV, T, D), q.dtype)] * 2,
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)],
            compiler_params=_grouped_params(
                pltpu, ("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, out, lse)

    return call


def _fold_group(x, KV):
    """[B, T, KV * G, D] -> [B * KV, G, T, D]: query head `h` shares
    the kv head `h // G`."""
    B, T, H, D = x.shape
    return x.reshape(B, T, KV, H // KV, D).transpose(0, 2, 3, 1, 4).reshape(
        B * KV, H // KV, T, D)


def _unfold_group(x, B):
    BKV, G, T, D = x.shape
    return x.reshape(B, BKV // B, G, T, D).transpose(0, 3, 1, 2, 4).reshape(
        B, T, (BKV // B) * G, D)


def _blocks(q, block_q: int, block_k: int):
    """(block_q, block_k) clamped to T, or a ValueError naming the
    shape the kernels cannot tile."""
    B, T, H, D = q.shape
    block_q, block_k = min(block_q, T), min(block_k, T)
    if T % block_q or T % block_k or D % 8:
        raise ValueError(
            f"flash_attention cannot tile q/k/v {tuple(q.shape)} "
            f"[B, T, H, D] with blocks ({block_q}, {block_k}): T must "
            "be a multiple of both blocks and D of 8 — pad the "
            "sequence or use attention=\"dense\""
        )
    return block_q, block_k


def _fold(x):
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _unfold(x, B, H):
    BH, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: bool = False, window=None):
    """q [B, T, H, D], k / v [B, T, KV, D] -> [B, T, H, D].  `KV` may
    divide `H` (grouped heads: query head `h` reads kv head `h // (H /
    KV)`), and `window` (None: the whole prefix) keeps for row `i` the
    columns `i - window < j <= i`; both are causal only."""
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret, window)
    return out


def _is_grouped(q, k, causal, window):
    """Whether the call takes the grouped kernels: fewer kv heads than
    query heads, or a window.  Every other call is what it was."""
    H, KV = q.shape[2], k.shape[2]
    if H == KV and window is None:
        return False
    if not causal or H % KV or (window is not None and window < 1):
        raise ValueError(
            f"flash_attention: q {tuple(q.shape)} against k "
            f"{tuple(k.shape)} with window {window}: grouped heads and "
            "a window are causal, and the kv heads divide the query heads")
    return True


def _fwd(q, k, v, causal, block_q, block_k, interpret, window=None):
    B, T, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    if _is_grouped(q, k, causal, window):
        KV = k.shape[2]
        _blocks(q, block_q, block_k)  # the same shapes are refused
        bq, bk = grouped_blocks(T, H // KV, block_q, block_k)
        fwd = _build_fwd_grouped(scale, T, H // KV, bq, bk, window,
                                 interpret, q.dtype)
        out, lse = fwd(_fold_group(q, KV), _fold(k), _fold(v))
        out = checkpoint_name(_unfold_group(out, B).reshape(B, T, H * D),
                              "flash_out")
        # [B*KV, T / block_q, 1, G * block_q] -> [B*KV, G, T]
        lse = checkpoint_name(
            lse.reshape(B * KV, T // bq, H // KV, bq).transpose(
                0, 2, 1, 3).reshape(B * KV, H // KV, T), "flash_lse")
        return out.reshape(B, T, H, D), (q, k, v, lse, out)
    block_q, block_k = _blocks(q, block_q, block_k)
    n_k = T // block_k
    fwd = _build_fwd(causal, scale, block_q, block_k, n_k, interpret,
                     q.dtype, (_sub_tile(block_q), _sub_tile(block_k)))
    out, lse = fwd(_fold(q), _fold(k), _fold(v))
    # the two residuals worth more than their bytes (FLASH_RESIDUALS):
    # out as the caller's out-projection reads it, [B, T, H*D] (folded
    # [B*H, T, 64] it is lane-padded to twice the bytes in HBM), and
    # lse WITHOUT its singleton lane dim (f32[B*H, T, 1]: 128 x)
    out = checkpoint_name(_unfold(out, B, H).reshape(B, T, H * D),
                          "flash_out")
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    return out.reshape(B, T, H, D), (q, k, v, lse, out)


def _bwd_split(causal, block_q, block_k, interpret, window, res, g):
    """Past the fused kernel's limit: the split pair, the group (of ONE
    query head where the heads are equal) in the rows."""
    q, k, v, lse, out = res
    B, T, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    bq, bk = grouped_blocks(T, G, block_q, block_k, _GROUP_ROWS_BWD)
    args = (_fold_group(q, KV), _fold(k), _fold(v), _fold_group(g, KV),
            _fold_group(out.reshape(q.shape), KV),
            lse.reshape(B * KV, G, T, 1))
    build = (1.0 / (D ** 0.5), T, G, bq, bk, window, interpret, q.dtype,
             causal)
    dq = _build_bwd_dq_grouped(*build)(*args)
    dk, dv = _build_bwd_dkv_grouped(*build)(*args)
    return _unfold_group(dq, B), _unfold(dk, B, KV), _unfold(dv, B, KV)


def _bwd_grouped(block_q, block_k, interpret, window, res, g):
    """The grouped call's backward: under the fused kernel's limit (the
    blocks hold T) that kernel a query head, its dK and dV summed over
    the group here; past it the split pair."""
    q, k, v, lse, out = res
    B, T, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    if min(block_q, block_k) < T:
        return _bwd_split(True, block_q, block_k, interpret, window, res, g)
    fused = _build_bwd_fused(True, 1.0 / (D ** 0.5), T, interpret, q.dtype,
                             _sub_tile(T), window, G)
    dq, dk, dv = fused(_fold(q), _fold(k), _fold(v), _fold(g),
                       lse.reshape(B * H, T, 1), _fold(out.reshape(q.shape)))
    group_sum = lambda x: jnp.sum(  # noqa: E731
        x.astype(jnp.float32).reshape(B, KV, G, T, D), axis=2
    ).astype(x.dtype).transpose(0, 2, 1, 3)
    return _unfold(dq, B, H), group_sum(dk), group_sum(dv)


def _bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, lse, out = res
    if _is_grouped(q, k, causal, window):
        return _bwd_grouped(block_q, block_k, interpret, window, res, g)
    B, T, H, D = q.shape
    block_q, block_k = _blocks(q, block_q, block_k)
    if block_q < T or block_k < T:
        return _bwd_split(causal, block_q, block_k, interpret, None, res, g)
    out_folded = _fold(out.reshape(q.shape))
    lse = lse[..., None]  # [BH, T, 1], as the kernel takes it
    fused = _build_bwd_fused(causal, 1.0 / (D ** 0.5), T, interpret, q.dtype,
                             _sub_tile(T))
    dq, dk, dv = fused(_fold(q), _fold(k), _fold(v), _fold(g), lse,
                       out_folded)
    return _unfold(dq, B, H), _unfold(dk, B, H), _unfold(dv, B, H)


flash_attention.defvjp(_fwd, _bwd)


def checkpoint_block(fn):
    """`jax.checkpoint(fn)` for a transformer block that a `lax.scan`
    walks: everything is recomputed in the backward pass but what the
    flash kernel names (`FLASH_RESIDUALS`).  A block that holds no such
    name (dense, ring or ulysses attention; the kernel inside a
    `shard_map`) keeps nothing, as under a bare `jax.checkpoint`.

    `prevent_cse=False`: the forward and the replay of a scanned block
    live in two different loops, where nothing can merge them, and the
    barriers that would prevent it keep XLA from fusing a layer's
    slices of the stacked weights and activations into their readers
    (4 ms of a 407 ms step at gpt2-medium, PERF.md section 6, PR 46)."""
    return jax.checkpoint(
        fn, prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUALS))
