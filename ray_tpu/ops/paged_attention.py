"""Paged flash-decode attention as Pallas TPU kernels.

The serve engine's per-chip decode lever: decode attention that reads
the paged KV pool THROUGH the block tables instead of gathering every
sequence's blocks into a dense view and scattering them back each
chunk (vLLM's PagedAttention, Kwon et al. SOSP 2023, fused with the
split-KV walk of Flash-Decoding, Dao et al. 2023).  This is the
opposite regime from the MXU-bound lm-head where Pallas measurably
lost (PERF.md round 5): decode attention is memory-bound over the KV
pool, and the gather path pays two extra full passes over the live KV
per chunk (pool -> dense copy, dense -> pool scatter) plus pow-2
padding on the gather width — pure HBM bandwidth the kernel never
spends.

Two kernels, both taking the pool `[L, num_blocks, block_size, KV,
hd]` whole with the LAYER INDEX as a scalar-prefetch argument, so the
engine's per-layer scan never slices (= copies) the pool:

- `paged_kv_append`: writes one new KV row per sequence into its tail
  block, in place (`input_output_aliases`) — the grid touches ONE
  block per row, replacing the chunk stepper's whole-view scatter.
- `paged_decode_attention`: ONE grid step whose own loops walk rows
  and, per row, COMPUTE BLOCKS of P pages (`_pages_per_block`: the
  longest block the score tile allows, from `H`, `KV`, the block size
  and the table's width alone: 128 tokens = 8 pages for the per-head
  forms at the serving shapes, 512 or 256 for a folded or a latent pool,
  the whole table where it is narrower).  The walk ends at the row's
  last live page (`pos[b]`): a per-head row of 200 tokens takes two
  blocks whether its table is 16, 64 or 81 wide, and a page a row does
  not have is neither copied nor stepped over.  A DEAD row, one that
  owes no token in this step (the engine's `pos >= stop`), is handed
  position -1 and has no live page: no copy, no block, a row of zeros
  out, and the next row's first copies start in its place; the append
  kernel is handed a position past the table's reach and writes
  nothing for it (`dead_row_positions`).  The
  pools stay in HBM; a block's live pages are copied through
  the row's table into one of two VMEM tiles (`make_async_copy`, page
  by page: pages are scattered, so no `BlockSpec` describes the tile)
  while the block before it is folded, across row boundaries too.  A
  page is taken as it lies in the pool, `[BS * KV, hd]` rows ordered
  (token, kv head) — the wrapper's reshape is a bitcast — and the
  block's tile meets ALL query heads at once: one score product
  `[H, hd] x tile^T` with other kv heads' columns masked, one exp on
  full lanes, one value product in which those columns weigh zero, one
  update of the running max / sum / f32 accumulator.  That spends `KV`
  times the MXU columns a per-head product would, and removes what a
  page-at-a-time, head-at-a-time walk spends its time on: per 16
  tokens eight sublane slices `k[:, h, :]`, eight sliver dots and eight
  softmax chains on 6%-full registers (2.6 us against 0.08 us of DMA;
  PERF.md section 6, PR 26).  Tokens past `pos[b]` (in a row's last
  block) have their scores masked to -1e30 and V's rows to zero, so
  nothing past the position — table padding, the scratch block, a
  tile's stale bytes — reaches a result, not even as `0 * NaN`.
  One result, `[B, H, hd]`: the benchmark finds the kernel in a trace
  by that shape.  The single grid step is a deliberate trade for the
  one-core v5e: rows are walked serially, where a ("parallel",
  "arbitrary") grid over rows would let a two-core chip (v4, v5p)
  split them.  There, restore it as a grid over halves of the rows
  (`grid=(2,)`, "parallel", each step walking `B // 2` rows with its
  own prefetch chain); a step per row would give up the prefetch
  across rows and pay Pallas's per-step cost 64 times a call.  With
  MHA (`KV == H`, llama1b4) `KV - 1` of every `KV` score columns are
  masked; the MXU is idle in decode, so the call still follows the
  bytes (62% of the HBM peak at llama1b4's shapes, 49% at Mistral's:
  PERF.md section 5's microbenchmark).

Numerics mirror `llama.decode_step_rows`' dense attention exactly in form
(q.k^T with f32 accumulation, -1e30 mask, softmax weights cast to the
compute dtype for the value matmul, f32 value accumulation); the
reduction is blockwise-online rather than dense, so logits agree to
float rounding and greedy argmax is preserved (pinned by
`tests/test_paged_attention.py`).

Int8 KV rides the same kernels: pools carry int8 payload plus a
per-row, per-kv-head f32 scale sidecar `[L, num_blocks, block_size,
KV]` stored blockwise beside the pool; dequantization is fused inside
the attention kernel (K and V cross HBM as int8 and are never written
back dequantized: a column's K scale multiplies its score, its V scale
its weight) and the append kernel writes the quantized row + its
scale.  The SCALES take a longer way than the payload: Mosaic cannot
cut a `[BS, KV]` page out of a sidecar whose minor dim is narrower
than a tile, so the wrapper gathers every row's scales through its
table, `W` pages wide whatever the row holds, into two f32 arrays
`[B, blocks, 1, P * BS * KV]` (2 MB each at 64 slots and `W` 64; an
XLA gather that writes them and a kernel that reads the live blocks
back, per layer call), which is 1/32 of the payload's bytes on a full
table and more than the payload on a nearly empty one.  On the v5e it
makes the int8 call SLOWER than the bf16 one and dependent on `W`
again (170 us at `W` 16, 250 at `W` 64, against 105 for bf16 on the
same rows; PERF.md section 5): int8 KV buys pool capacity here, not
time, until the scales lie in the pool in a form the kernel can copy.

HEADS NARROWER THAN A LANE TILE (`head_dim` 64: `models/lfm2.py`) lie
ALL to one pool row, side by side: the pool is `[L, num_blocks,
block_size, KV * hd]` (`kv_pool_tail`), the same bytes in the same
order as `[..., KV, hd]`, but with whole lanes as the minor dimension
and a block's tokens as its rows, the layout the latent pool has.  A
`[.., KV, 64]` pool the TPU would pad to 128 lanes in HBM (twice the
bytes, and Mosaic refuses to cut a 64-wide page out of it), so the
kernels never see one.  They run UNCHANGED on the folded pool as ONE kv
head of width `KV * hd`: the wrapper lays each query into the lanes of
its own kv head (zeros in the others', so the score is the narrow
head's), passes the narrow head's scale, and takes each query head's
own lanes of the result; a score tile then has a column a token, no
head to mask, and a quarter of the exponentials of the per-head tile:
which is why its block may be four times as long (`_pages_per_block`).
The append takes `[B, KV, hd]` rows as the `[B, KV * hd]` they are,
through the one-row form the latent pool uses, for both pools in one
call.  The MXU multiplies `KV` times the columns, which decode does not
notice.

The kernels are COMPILED for the TPU unless the caller passes
`interpret=True` (the CPU tests do); nothing here looks at the backend.
`tests/test_aot_tpu_compile.py` lowers every variant for a described
v5e chip at serving widths, so a block shape Mosaic refuses fails
tier-1 instead of the first chip run.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


# ----------------------------------------------------------------------
# int8 helpers (shared with the engine's gather fallback + weight quant)
# ----------------------------------------------------------------------
def quantize_int8(x: jax.Array, axis: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-slice int8 quantization along `axis` in f32 math:
    scale = max|x| / 127 (so the max element maps to exactly ±127 and a
    dequant->requant round trip is IDEMPOTENT — stored KV never drifts
    when the gather fallback rewrites untouched rows), zero slices get
    scale 0 and payload 0.  Returns (q int8, scale f32 with `axis`
    removed)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = amax / 127.0
    q = jnp.round(xf / jnp.where(scale == 0.0, 1.0, scale))
    q = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis=axis)


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype,
                    axis: int = -1) -> jax.Array:
    """Inverse of `quantize_int8`: f32 multiply, then cast to `dtype`."""
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scale, axis)).astype(dtype)


LANES = 128  # a TPU tile's minor dimension


def kv_pool_tail(kv_heads: int, head_dim: int) -> Tuple[int, ...]:
    """What one token caches in a K or V pool: `(KV, hd)`, or with
    heads narrower than a lane tile all of them side by side in ONE
    row of whole lanes, `(KV * hd,)` (see the module docstring).  Rows
    that would not fill whole lanes stay as they are."""
    if head_dim % LANES and (kv_heads * head_dim) % LANES == 0:
        return (kv_heads * head_dim,)
    return kv_heads, head_dim


# ----------------------------------------------------------------------
# append kernel: one KV row into each sequence's tail block, in place
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=32)
def _build_append(L, NB, BS, KV, HD, B, W, pool_dtype, new_dtype,
                  quantized, interpret, pools=2, flat=False, vd=0, new_rows=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    view = W * BS  # positions the W-wide table can address

    def pool_map(b, layer_ref, tables_ref, pos_ref):
        # tail block of row b; clamped so that a position past the
        # table's reach (a dead row's: `dead_row_positions`) maps the
        # table's LAST block, copied through unchanged (scratch padding
        # for a slot without a table), instead of reading out of bounds
        w = jnp.minimum(pos_ref[b] // BS, W - 1)
        # a page is [BS, KV, HD], or [BS, HD] in the flat form
        return (layer_ref[0], tables_ref[b, w]) + (0,) * (2 if flat else 3)

    def scale_map(b, layer_ref, tables_ref, pos_ref):
        w = jnp.minimum(pos_ref[b] // BS, W - 1)
        return (layer_ref[0], tables_ref[b, w], 0, 0)

    def row_map(b, *_refs):
        return (b, 0, 0)

    # per-row scales ride as [B, 1, KV]: Mosaic wants a block's last
    # two dims tile-aligned or equal to the array's, and (1, KV) of a
    # [B, 1, KV] array is the latter where (KV,) of [B, KV] is neither
    srow_map = row_map

    if quantized:
        def kernel(layer_ref, tables_ref, pos_ref, kp_ref, vp_ref,
                   ks_ref, vs_ref, kn_ref, vn_ref, kns_ref, vns_ref,
                   kp_out, vp_out, ks_out, vs_out):
            b = pl.program_id(0)
            p_b = pos_ref[b]
            off = p_b % BS
            # copy-through: the out block is staged whole, so rows the
            # kernel doesn't write must be re-written from the input
            kp_out[...] = kp_ref[...]
            vp_out[...] = vp_ref[...]
            ks_out[...] = ks_ref[...]
            vs_out[...] = vs_ref[...]

            @pl.when(p_b < view)
            def _write():  # matches the gather path's masked select:
                # a position past the table's reach writes nothing
                kp_out[pl.ds(off, 1)] = kn_ref[...].reshape(1, KV, HD)
                vp_out[pl.ds(off, 1)] = vn_ref[...].reshape(1, KV, HD)
                ks_out[pl.ds(off, 1)] = kns_ref[...].reshape(1, KV)
                vs_out[pl.ds(off, 1)] = vns_ref[...].reshape(1, KV)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, None, BS, KV, HD), pool_map),
                pl.BlockSpec((None, None, BS, KV, HD), pool_map),
                pl.BlockSpec((None, None, BS, KV), scale_map),
                pl.BlockSpec((None, None, BS, KV), scale_map),
                pl.BlockSpec((None, KV, HD), row_map),
                pl.BlockSpec((None, KV, HD), row_map),
                pl.BlockSpec((None, 1, KV), srow_map),
                pl.BlockSpec((None, 1, KV), srow_map),
            ],
            out_specs=[
                pl.BlockSpec((None, None, BS, KV, HD), pool_map),
                pl.BlockSpec((None, None, BS, KV, HD), pool_map),
                pl.BlockSpec((None, None, BS, KV), scale_map),
                pl.BlockSpec((None, None, BS, KV), scale_map),
            ],
        )
        out_shape = [
            jax.ShapeDtypeStruct((L, NB, BS, KV, HD), pool_dtype),
            jax.ShapeDtypeStruct((L, NB, BS, KV, HD), pool_dtype),
            jax.ShapeDtypeStruct((L, NB, BS, KV), jnp.float32),
            jax.ShapeDtypeStruct((L, NB, BS, KV), jnp.float32),
        ]
        # operand indices are FLATTENED and include the 3 scalar-
        # prefetch args (megablox gmm convention)
        aliases = {3: 0, 4: 1, 5: 2, 6: 3}
    else:
        # `pools`: 2 = a K and a V pool, 1 = one latent pool.  `flat`:
        # the pools are `[L, NB, BS, HD]`, nothing per head (MLA's
        # latent; K and V with every head folded into the row), so a
        # page is a plain `[BS, HD]` tile and a new row `[1, HD]`;
        # else `[L, NB, BS, KV, HD]`.  `vd` (flat, two pools): the V
        # pool's rows are that wide where K's are `HD`
        # `new_rows` > 1 (flat): a slot writes that many CONSECUTIVE
        # rows from `pos` on, all inside one page (`pos` and BS are
        # multiples of it: a block of a block-diffusion step)
        page = (BS, HD) if flat else (BS, KV, HD)
        row = (new_rows, HD) if flat else (KV, HD)
        pages = [page] * pools
        rows = [row] * pools
        if vd:
            pages[1], rows[1] = (BS, vd), (new_rows, vd)

        def kernel(layer_ref, tables_ref, pos_ref, *refs):
            ins, news, outs = (refs[:pools], refs[pools:2 * pools],
                               refs[2 * pools:])
            b = pl.program_id(0)
            p_b = pos_ref[b]
            off = p_b % BS
            if flat:
                # a `[BS, HD]` page packs two bf16 rows a sublane, and
                # Mosaic stores a single row only at an offset it can
                # prove aligned: select the row into the whole page
                if new_rows == 1:
                    hit = (jax.lax.broadcasted_iota(jnp.int32, (BS, 1), 0)
                           == off) & (p_b < view)
                    for src, new, out in zip(ins, news, outs):
                        out[...] = jnp.where(hit, new[...], src[...])
                    return
                at = jax.lax.broadcasted_iota(jnp.int32, (BS, 1), 0) - off
                # page row `off + r` takes new row r: a one-hot product
                # places the rows (exact: one term a sum), so no packed
                # sublane is sliced
                hit = (at >= 0) & (at < new_rows) & (p_b < view)
                place = (at == jax.lax.broadcasted_iota(
                    jnp.int32, (BS, new_rows), 1))
                for src, new, out in zip(ins, news, outs):
                    moved = jax.lax.dot_general(
                        place.astype(new.dtype), new[...],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    out[...] = jnp.where(hit, moved.astype(out.dtype),
                                         src[...])
                return
            for src, out in zip(ins, outs):
                out[...] = src[...]

            @pl.when(p_b < view)
            def _write():
                for new, out in zip(news, outs):
                    out[pl.ds(off, 1)] = new[...].reshape(1, KV, HD)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=(
                [pl.BlockSpec((None, None) + pg, pool_map) for pg in pages]
                + [pl.BlockSpec((None,) + r, row_map) for r in rows]),
            out_specs=[pl.BlockSpec((None, None) + pg, pool_map)
                       for pg in pages],
        )
        out_shape = [jax.ShapeDtypeStruct((L, NB) + pg, pool_dtype)
                     for pg in pages]
        aliases = {3 + i: i for i in range(pools)}

    return pl.pallas_call(
        kernel,
        name="paged_kv_append" if pools == 2 else "mla_paged_kv_append",
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            # dead rows share the scratch tail block: the grid must stay
            # sequential so their copy-through writes don't race
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )


def paged_kv_append(k_pool, v_pool, k_new, v_new, tables, pos, layer, *,
                    k_scale=None, v_scale=None, k_new_scale=None,
                    v_new_scale=None, interpret: bool = False,
                    rows: int = 1):
    """Write each row's new KV into its tail pool block, in place.

    k_pool/v_pool [L, NB, BS, KV, hd]; k_new/v_new [B, KV, hd] (pool
    dtype; for a folded pool `[L, NB, BS, KV * hd]`, `kv_pool_tail`,
    the rows are taken as the `[B, KV * hd]` they are, and a folded V
    pool may be of another width than K's, `[L, NB, BS, KV * hd_v]`);
    tables [B, W] int32; pos [B] int32 (the position being
    written); layer: scalar int32 (traced OK).  With the int8 sidecar
    (`k_scale`/`v_scale` [L, NB, BS, KV] f32 + per-row `k_new_scale`/
    `v_new_scale` [B, KV]) returns (k_pool, v_pool, k_scale, v_scale),
    else (k_pool, v_pool).

    `rows` > 1 (a folded pool only): each sequence writes that many
    CONSECUTIVE rows, `k_new` / `v_new` `[B, rows, KV * hd]`, at `pos ..
    pos + rows - 1`; `pos` and the block size are multiples of `rows`,
    so the rows lie in one page (a block-diffusion step's block, which
    every forward of the block writes again)."""
    B, W = tables.shape
    quantized = k_scale is not None
    if k_pool.ndim == 4:  # folded rows: the flat form, both pools
        L, NB, BS, HD = k_pool.shape
        VD = v_pool.shape[-1]
        assert not quantized, "a folded pool has no int8 scales wired"
        assert BS % rows == 0, (BS, rows)
        fn = _build_append(L, NB, BS, 1, HD, B, W,
                           jnp.dtype(k_pool.dtype).name,
                           jnp.dtype(k_new.dtype).name, False,
                           bool(interpret), flat=True,
                           vd=VD if VD != HD else 0, new_rows=rows)
        return tuple(fn(jnp.asarray(layer, jnp.int32).reshape(1), tables,
                        pos, k_pool, v_pool, k_new.reshape(B, rows, HD),
                        v_new.reshape(B, rows, VD)))
    assert rows == 1, "several rows a sequence: a folded pool's form"
    L, NB, BS, KV, HD = k_pool.shape
    fn = _build_append(L, NB, BS, KV, HD, B, W,
                       jnp.dtype(k_pool.dtype).name,
                       jnp.dtype(k_new.dtype).name, quantized,
                       bool(interpret))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if quantized:
        return tuple(fn(layer, tables, pos, k_pool, v_pool, k_scale,
                        v_scale, k_new, v_new,
                        k_new_scale.reshape(B, 1, KV),
                        v_new_scale.reshape(B, 1, KV)))
    return tuple(fn(layer, tables, pos, k_pool, v_pool, k_new, v_new))


def dead_row_positions(pos, live, tables, block_size: int):
    """The positions the two kernels take for a step in which only the
    rows `live` [B] (bool; None: every row) owe a token: `(append_pos,
    attend_pos)`.  A live row keeps `pos[b]` in both.  A DEAD row (the engine's `pos >=
    stop`: a slot never used, a budget that ended inside the chunk, a
    finished row not harvested yet) appends at the first position past
    its table's reach, which the append kernels reject (`p_b < view`),
    so it WRITES NOTHING: a finished row still holds its real table,
    whose blocks the radix cache may share.  It attends at -1, which
    the attention kernels take as a row with nothing to read: no page
    copied, no block folded, a row of zeros out."""
    if live is None:
        return pos, pos
    view = tables.shape[1] * block_size
    return jnp.where(live, pos, view), jnp.where(live, pos, -1)


# A latent pool's rows are padded to whole lanes.  Mosaic cuts a page
# out of the pool only along whole (8, 128) tiles, and the TPU lays a
# 576-wide bf16 array out 640 wide in HBM whatever its logical shape, so
# the padding costs the device no byte a 576-wide pool would not cost;
# the roofline counts the 576 that carry values.
MLA_LANES = LANES


def mla_pool_width(d: int) -> int:
    return -(-d // MLA_LANES) * MLA_LANES


def mla_paged_kv_append(pool, new, tables, pos, layer, *,
                        interpret: bool = False):
    """The latent form of `paged_kv_append`: ONE pool `[L, NB, BS, D]`
    and one new row `new` [B, d] a sequence (d = compressed KV + rotary
    key, 576 for DeepSeek-V3's attention; D = d rounded up to whole
    lanes, see `MLA_LANES`; the columns past d are written as zeros).
    Returns the pool."""
    L, NB, BS, D = pool.shape
    B, W = tables.shape
    new = jnp.pad(new, ((0, 0), (0, D - new.shape[-1])))
    fn = _build_append(L, NB, BS, 1, D, B, W, jnp.dtype(pool.dtype).name,
                       jnp.dtype(new.dtype).name, False, bool(interpret),
                       pools=1, flat=True)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return fn(layer, tables, pos, pool, new.reshape(B, 1, D))[0]


# ----------------------------------------------------------------------
# decode attention kernel: split-KV walk over the block table
# ----------------------------------------------------------------------
# A compute block is the LONGEST its shapes allow: this many tokens of a
# row, fewer where the score tile [H, tokens * KV] f32 would outgrow the
# 32 vector registers' worth the softmax works in.  A longer block
# spreads the per-block costs (DMA waits, the accumulator's rescale, the
# softmax chain's latency) over more tokens: the latent form 0.61 / 0.47
# / 0.40 ms a call at 128 / 256 / 512 with 64 rows of 1,500 live tokens
# (PERF.md section 6, PR 27); the folded forms' timings: PR 60's
# paragraph there.  The score tile is what tells the forms apart, and no
# form is asked its name: with `KV` heads a token the tile is `KV` times
# wider, so the per-head forms (KV 8 or 16 at H 32 or 16) stay at 128
# tokens, and the forms the kernel sees as ONE kv head (the latent pool,
# a folded pool) take 512 at up to 64 query heads, 256 at 128.  The four
# K / V tiles (two of each, so that the next block's copies run under
# this block's arithmetic) are then up to 2.6 MB of VMEM, which
# `_vmem_limit` counts
_BLOCK_TOKENS = 512
_SCORE_TILE_BYTES = 128 * 1024


def _pages_per_block(BS, KV, H, W):
    """Pages folded per step of the walk, from the shapes alone: what
    the score tile allows up to `_BLOCK_TOKENS` (at BS 16: 8 pages for
    KV 8 x H 32 and KV 16 x H 16; for one kv head 32 pages up to H 64,
    16 at H 128); the whole table where it is narrower; never less than
    one."""
    by_score = _SCORE_TILE_BYTES // (4 * H * KV * BS)
    return max(1, min(W, _BLOCK_TOKENS // BS, by_score))


@functools.lru_cache(maxsize=32)
def _build_attention(L, NB, BS, KV, HD, B, W, H, pool_dtype, q_dtype,
                     quantized, interpret, latent=0, scale=None, vd=0):
    """`latent` > 0 is the MLA form: ONE pool whose row is the latent
    (`KV` 1, `HD` the score width, 576), all `H` query heads score
    against it, and the VALUE is the first `latent` columns (512) of
    the very tile the scores were taken on: the pool is read once, no
    V pool exists, and with one kv head the head mask falls away.
    `scale` is then the caller's (1 / sqrt(192): the width of the
    un-absorbed query, not of the latent).  `vd` > 0: the V pool's rows
    are `vd` wide where K's are `HD` (a folded pool of heads whose keys
    and values differ in width), and so is the result."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert not (latent and (quantized or KV != 1))
    assert not (vd and (latent or quantized or KV != 1))
    group = H // KV
    scale = HD ** -0.5 if scale is None else scale
    VD = latent or vd or HD  # width of a value row, and of the result
    q_dt = jnp.dtype(q_dtype)
    pool_dt = jnp.dtype(pool_dtype)
    P = _pages_per_block(BS, KV, H, W)
    T = P * BS   # tokens in a compute block
    R = BS * KV  # rows of one page: (token, kv head) pairs, token-major
    C = P * R    # ... and of a compute block: the score tile's columns
    cap = W * BS - 1  # last position the table can address

    def kernel(layer_ref, tables_ref, pos_ref, q_ref, k_hbm, *rest):
        if quantized:
            (v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf,
             sems) = rest
        elif latent:
            o_ref, k_buf, sems = rest
        else:
            v_hbm, o_ref, k_buf, v_buf, sems = rest
        layer = layer_ref[0]

        def last_pos(b):
            # the table's reach bounds a position from above; -1 (any
            # negative position) is a row with nothing to read
            return jnp.clip(pos_ref[b], -1, cap)

        def block_copies(b, blk, slot, each):
            """`each` (start or wait) on every copy of block `blk` of
            row b: the pages the row HAS there, and no other."""
            n_live = jnp.minimum((last_pos(b) + BS) // BS - blk * P, P)

            def page_copies(j, _):
                page = tables_ref[b, blk * P + j]
                rows = pl.ds(pl.multiple_of(j * R, R), R)
                each(pltpu.make_async_copy(
                    k_hbm.at[layer, page], k_buf.at[slot, rows],
                    sems.at[0, slot]))
                if not latent:
                    each(pltpu.make_async_copy(
                        v_hbm.at[layer, page], v_buf.at[slot, rows],
                        sems.at[1, slot]))

            jax.lax.fori_loop(0, n_live, page_copies, None)
            if quantized:
                @pl.when(n_live > 0)
                def _scales():
                    each(pltpu.make_async_copy(
                        ks_hbm.at[b, blk], ks_buf.at[slot],
                        sems.at[0, slot]))
                    each(pltpu.make_async_copy(
                        vs_hbm.at[b, blk], vs_buf.at[slot],
                        sems.at[1, slot]))

        # column c of a score tile is (token c // KV, kv head c % KV),
        # as a page's rows lie in the pool; query head r attends
        # through kv head r // group only
        col = jax.lax.broadcasted_iota(jnp.int32, (H, C), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (H, C), 0)
        own_head = jax.lax.rem(col, KV) == jax.lax.div(row, group)
        col_tok = jax.lax.div(col, KV)
        row_tok = jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0), KV)

        def fold(b, i, n_blk, q, carry):
            """Compute block i of row b into the online softmax; its
            copies are in flight, the next block's are started here."""
            m, l, acc, slot = carry
            last = i + 1 >= n_blk
            nxt_b = jnp.where(last, b + 1, b)

            @pl.when(nxt_b < B)
            def _prefetch():
                block_copies(nxt_b, jnp.where(last, 0, i + 1), 1 - slot,
                             lambda c: c.start())

            block_copies(b, i, slot, lambda c: c.wait())
            k = k_buf[slot]
            # latent: the value is the compressed part of the same rows
            v = k[:, :latent] if latent else v_buf[slot]
            if quantized:
                # int8 is exact in the compute dtype; a column's scale
                # multiplies its score and, for V, its weight
                k, v = k.astype(q_dt), v.astype(q_dt)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if quantized:
                s = s * ks_buf[slot]
            live = last_pos(b) - i * T  # the block's last live token
            valid = col_tok <= live
            if KV > 1:
                valid = own_head & valid
            s = jnp.where(valid, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            # a walked block's first token is live, so m_new is a real
            # score and a masked column's weight exp(-1e30 - m_new) is 0
            p = jnp.exp(s - m_new)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            # what lies past the position (in a row's last block: a
            # page never copied holds any bits, a NaN's too) must not
            # meet even a zero weight
            if quantized:
                p = jnp.where(valid, p * vs_buf[slot], 0.0)
            v = jnp.where(row_tok <= live, v, jnp.zeros_like(v))
            # softmax weights cast to the compute dtype for the value
            # matmul, f32 accumulation — decode_step_rows' dense form; another
            # head's columns weigh zero, so one product serves all
            acc = acc * corr + jax.lax.dot_general(
                p.astype(q_dt), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l, acc, 1 - slot

        block_copies(0, 0, 0, lambda c: c.start())

        def row_body(b, slot):
            n_blk = (last_pos(b) + T) // T
            q = q_ref[b]

            @pl.when((n_blk == 0) & (b + 1 < B))
            def _skip():  # no block folds, so none starts the next
                # row's first copies: start them here, into the tile
                # this row did not use
                block_copies(b + 1, 0, slot, lambda c: c.start())

            _, l, acc, slot = jax.lax.fori_loop(
                0, n_blk, lambda i, c: fold(b, i, n_blk, q, c),
                (jnp.full((H, 1), _NEG_INF, jnp.float32),
                 jnp.zeros((H, 1), jnp.float32),
                 jnp.zeros((H, VD), jnp.float32), slot))
            # a row that read nothing (l == 0) is a row of zeros
            o_ref[b] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(
                o_ref.dtype)
            return slot

        jax.lax.fori_loop(0, B, row_body, 0)

    whole = pl.BlockSpec((B, H, HD), lambda *_: (0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    # the walk's tiles, two slots each: K, V, an int8 pool's scales
    tiles = [((2, C, HD), pool_dt)]
    if not latent:
        tiles.append(((2, C, VD), pool_dt))
    if quantized:
        tiles += [((2, 1, C), jnp.dtype(jnp.float32))] * 2
    scratch = [pltpu.VMEM(shape, dt) for shape, dt in tiles]
    scratch.append(pltpu.SemaphoreType.DMA((2, 2)))  # [K | V, slot]

    return pl.pallas_call(
        kernel,
        name=("mla_paged_decode_attention" if latent
              else "paged_decode_attention"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # one step: the walk over rows and their blocks is the
            # kernel's own loop, so it takes no step for a page a row
            # does not have and prefetches across rows (a trade for the
            # one-core v5e: see the module docstring)
            grid=(1,),
            in_specs=[whole] + [in_hbm] * (
                4 if quantized else 1 if latent else 2),
            out_specs=pl.BlockSpec((B, H, VD), lambda *_: (0, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, VD), q_dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            **_vmem_limit(
                B * H * (HD + VD) * q_dt.itemsize,
                sum(math.prod(shape) * dt.itemsize for shape, dt in tiles)),
        ),
        interpret=interpret,
    )


# the queries and the result lie whole in VMEM, each in the two buffers
# a pipelined operand gets, beside the walk's own tiles (two of K and of
# V, and of an int8 pool's scales) and a flat 4 MB for what the fold
# keeps; where that is well past what Mosaic grants by default (16 MiB
# on a v5e, of 128 physical: 64 query heads against a folded row of 768
# lanes at 128 slots are 42 MB) the call asks for its own; the shapes
# that ran under the default run as they did
_VMEM_DEFAULT_BYTES = 24 << 20


def _vmem_limit(q_and_o_bytes: int, tile_bytes: int) -> dict:
    need = 2 * q_and_o_bytes + tile_bytes + (4 << 20)
    if need <= _VMEM_DEFAULT_BYTES:
        return {}
    return {"vmem_limit_bytes": need}


def paged_decode_attention(q, k_pool, v_pool, tables, pos, layer, *,
                           k_scale=None, v_scale=None,
                           interpret: bool = False):
    """One step of decode attention straight off the paged pool.

    q [B, H, hd] (post-RoPE, current positions); k_pool/v_pool
    [L, NB, BS, KV, hd]; tables [B, W] int32 block tables (pad with the
    scratch block); pos [B] int32 per-row positions — attention covers
    columns 0..pos[b] inclusive, so the current row must already be
    written (`paged_kv_append` first); a negative position is a row
    with nothing to read, whose result is zeros (a dead row:
    `dead_row_positions`).  `layer` scalar int32 selects
    the pool layer.  GQA: query head h attends through kv head
    h // (H // KV).  Returns o [B, H, hd] in q's dtype.

    A FOLDED pool (`kv_pool_tail`: `[L, NB, BS, KV * hd]`, q still
    `[B, H, hd]`): each query goes into the lanes of its own kv head,
    the kernel runs on one head of width `KV * hd` at the narrow
    head's scale, and each query head's own lanes come back.  The V
    pool's heads may be of another width, `[L, NB, BS, KV * hd_v]`: the
    result is then `[B, H, hd_v]`."""
    B, W = tables.shape
    H, hd = q.shape[1:]
    quantized = k_scale is not None
    if k_pool.ndim == 4:
        L, NB, BS, HD = k_pool.shape
        assert not quantized, "a folded pool has no int8 scales wired"
        KV, VD = HD // hd, v_pool.shape[-1]
        # query head h reads kv head h // group: lanes [g * hd, (g + 1) * hd)
        head = jnp.arange(H) // (H // KV)
        own = head[:, None] == jnp.arange(HD)[None, :] // hd     # [H, HD]
        q = jnp.where(own[None], jnp.tile(q, (1, 1, KV)),
                      jnp.zeros((), q.dtype))
        fn = _build_attention(L, NB, BS, 1, HD, B, W, H,
                              jnp.dtype(k_pool.dtype).name,
                              jnp.dtype(q.dtype).name, False,
                              bool(interpret), scale=hd ** -0.5,
                              vd=VD if VD != HD else 0)
        o = fn(jnp.asarray(layer, jnp.int32).reshape(1), tables, pos, q,
               k_pool, v_pool).reshape(B, H, KV, VD // KV)
        return jnp.take_along_axis(
            o, head[None, :, None, None], axis=2)[:, :, 0]
    L, NB, BS, KV, HD = k_pool.shape
    fn = _build_attention(L, NB, BS, KV, HD, B, W, H,
                          jnp.dtype(k_pool.dtype).name,
                          jnp.dtype(q.dtype).name, quantized,
                          bool(interpret))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # a page as its [BS * KV, hd] rows: the same bytes, no copy
    k_pool = k_pool.reshape(L, NB, BS * KV, HD)
    v_pool = v_pool.reshape(L, NB, BS * KV, HD)
    if not quantized:
        return fn(layer, tables, pos, q, k_pool, v_pool)
    P = _pages_per_block(BS, KV, H, W)

    def block_rows(scales):
        # Mosaic cannot slice a page's [BS, KV] scales out of the
        # sidecar (its minor dim is narrower than a tile), so each
        # row's are gathered through its table here and laid out as the
        # score tile's columns: [B, blocks, 1, P * BS * KV]
        rows = jnp.pad(scales[layer[0]][tables],
                       ((0, 0), (0, -W % P), (0, 0), (0, 0)))
        return rows.reshape(B, -1, 1, P * BS * KV)

    return fn(layer, tables, pos, q, k_pool, v_pool, block_rows(k_scale),
              block_rows(v_scale))


def mla_paged_decode_attention(q, pool, tables, pos, layer, *,
                               value_dim: int, scale: float,
                               interpret: bool = False):
    """One step of ABSORBED latent attention straight off the paged
    latent pool (DeepSeek-V2/V3's MLA in its decode form).

    q [B, H, D]: per head `q_nope W_uk` (width `value_dim`) beside the
    rotated `q_rope`; pool [L, NB, BS, D] holds, a token and layer, the
    normalised compressed KV (first `value_dim` columns) beside the
    rotated shared key; tables / pos / layer as `paged_decode_attention`
    (the current row already appended).  Scores `q . row * scale` over
    the live rows, softmax in float32, and the result is the weighted
    sum of the rows' first `value_dim` columns: o [B, H, value_dim],
    which the caller takes through `W_uv`."""
    L, NB, BS, D = pool.shape
    B, W = tables.shape
    H = q.shape[1]
    # zero query columns meet the pool's zero padding columns
    q = jnp.pad(q, ((0, 0), (0, 0), (0, D - q.shape[-1])))
    fn = _build_attention(L, NB, BS, 1, D, B, W, H,
                          jnp.dtype(pool.dtype).name,
                          jnp.dtype(q.dtype).name, False, bool(interpret),
                          latent=int(value_dim), scale=float(scale))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return fn(layer, tables, pos, q, pool)
