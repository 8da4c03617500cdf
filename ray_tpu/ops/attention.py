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
- split backward (`_build_bwd_dq`, `_build_bwd_dkv`: T > block): the
  FlashAttention-2 pair with the per-row logsumexp saved from forward;
  grid tiles above the diagonal are skipped and only tiles the diagonal
  crosses are masked.  No sub-tile walk yet: no benchmark cell runs it.
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
    sub-block from `_col_walk` (`tests/test_ops.py` holds the two views
    to the same counts)."""
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
        s = _causal_mask(s, *mask)
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


def _compute_live(causal, q0, block_q, k0, block_k, compute):
    """The split backward's tile: `compute(masked)`, masked only where
    the diagonal crosses it."""
    if causal:
        _when_live(q0, block_q, k0, block_k,
                   lambda: compute(False), lambda: compute(True))
    else:
        compute(False)


def _build_bwd_dq(causal, scale, block_q, block_k, n_k, interpret, dtype):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref, acc_ref):
        q0 = pl.program_id(1) * block_q
        k0 = pl.program_id(2) * block_k

        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def compute(masked):
            _, ds = _bwd_tile(
                q_ref[...], k_ref[...], v_ref[...], do_ref[...],
                lse_ref[...], dlt_ref[...], scale,
                (q0, k0) if masked else None)
            acc_ref[...] += _dot_f32(ds.astype(dtype), k_ref[...])

        _compute_live(causal, q0, block_q, k0, block_k, compute)

        @pl.when(pl.program_id(2) == n_k - 1)
        def _fin():
            dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)

    def call(q, k, v, do, lse, delta):
        BH, T, D = q.shape
        n_q = T // block_q
        return pl.pallas_call(
            kernel,
            name="flash_bwd_dq",
            grid=(BH, n_q, n_k),
            in_specs=[
                pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    return call


def _build_bwd_fused(causal, scale, T, interpret, dtype, sub):
    """Backward for the whole-sequence tile (block_q == block_k == T):
    with a (BH,) grid there is no cross-step accumulation, so dq/dk/dv
    come out of ONE kernel (the module docstring says how it walks)."""
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
            vis, full = _col_walk(c, sub, sub, n_sub) if causal else (0, 0)
            dk = dv = jnp.zeros((sub, q_ref.shape[-1]), jnp.float32)
            # the q sub-block the diagonal crosses, under the mask, then
            # every one below it as ONE strip
            for lo, hi, masked in ((vis * sub, full * sub, True),
                                   (full * sub, T, False)):
                if hi == lo:
                    continue
                rows = slice(lo, hi)
                qb, dob = q_ref[rows, :], do_ref[rows, :]
                p, ds = _bwd_tile(
                    qb, ks, vb, dob, lse_ref[rows, :], dlt_ref[rows, :],
                    None if fold else scale, (lo, c) if masked else None)
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
        return pl.pallas_call(
            kernel,
            name="flash_bwd_fused",
            grid=(BH,),
            in_specs=[spec, spec, spec, spec, vec, spec],
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


def _build_bwd_dkv(causal, scale, block_q, block_k, n_q, interpret, dtype):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
               dk_ref, dv_ref, dk_acc, dv_acc):
        k0 = pl.program_id(1) * block_k
        q0 = pl.program_id(2) * block_q

        @pl.when(pl.program_id(2) == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        def compute(masked):
            qb, dob = q_ref[...], do_ref[...]
            p, ds = _bwd_tile(
                qb, k_ref[...], v_ref[...], dob, lse_ref[...], dlt_ref[...],
                scale, (q0, k0) if masked else None)
            dv_acc[...] += _dot_f32(p.astype(dtype).T, dob)
            dk_acc[...] += _dot_f32(ds.astype(dtype).T, qb)

        # q blocks entirely above the diagonal see this kv block fully
        # masked: skipped
        _compute_live(causal, q0, block_q, k0, block_k, compute)

        @pl.when(pl.program_id(2) == n_q - 1)
        def _fin():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    def call(q, k, v, do, lse, delta):
        BH, T, D = q.shape
        n_k = T // block_k
        return pl.pallas_call(
            kernel,
            name="flash_bwd_dkv",
            grid=(BH, n_k, n_q),
            in_specs=[
                pl.BlockSpec((None, block_q, D), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((None, block_q, D), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, block_q, 1), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, block_q, 1), lambda b, j, i: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, T, D), q.dtype),
                jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    return call


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
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: bool = False):
    """q/k/v [B, T, H, D] -> [B, T, H, D]."""
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    B, T, H, D = q.shape
    block_q, block_k = _blocks(q, block_q, block_k)
    scale = 1.0 / (D ** 0.5)
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


def _bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, lse, out = res
    out_folded = _fold(out.reshape(q.shape))
    lse = lse[..., None]  # [BH, T, 1], as the kernels take it
    B, T, H, D = q.shape
    block_q, block_k = _blocks(q, block_q, block_k)
    scale = 1.0 / (D ** 0.5)
    n_q = T // block_q
    n_k = T // block_k
    qf, kf, vf, dof = _fold(q), _fold(k), _fold(v), _fold(g)
    if block_q == T and block_k == T:
        fused = _build_bwd_fused(causal, scale, T, interpret, q.dtype,
                                 _sub_tile(T))
        dq, dk, dv = fused(qf, kf, vf, dof, lse, out_folded)
        return _unfold(dq, B, H), _unfold(dk, B, H), _unfold(dv, B, H)
    delta = jnp.sum(
        dof.astype(jnp.float32) * out_folded.astype(jnp.float32),
        axis=-1, keepdims=True,
    )  # [BH, T, 1], matching lse's singleton lane dim
    dq_call = _build_bwd_dq(causal, scale, block_q, block_k, n_k,
                            interpret, q.dtype)
    dkv_call = _build_bwd_dkv(causal, scale, block_q, block_k, n_q,
                              interpret, q.dtype)
    dq = dq_call(qf, kf, vf, dof, lse, delta)
    dk, dv = dkv_call(qf, kf, vf, dof, lse, delta)
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
