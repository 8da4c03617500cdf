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
- `paged_decode_attention`: grid `(B, W)`; block tables and per-row
  positions ride in SMEM (`PrefetchScalarGridSpec`), each grid step
  DMAs pool block `tables[b, w]` and folds it into an online softmax
  (running max / sum / f32 accumulator in VMEM scratch) — the
  split-KV combine, one sequential axis per row.

Numerics mirror `llama.decode_step_vec`'s attention exactly in form
(q.k^T with f32 accumulation, -1e30 mask, softmax weights cast to the
compute dtype for the value matmul, f32 value accumulation); the
reduction is blockwise-online rather than dense, so logits agree to
float rounding and greedy argmax is preserved (pinned by
`tests/test_paged_attention.py`).

Int8 KV rides the same kernels: pools carry int8 payload plus a
per-row, per-kv-head f32 scale sidecar `[L, num_blocks, block_size,
KV]` stored blockwise beside the pool; dequantization is fused inside
the attention kernel (int8 payload is all that crosses HBM) and the
append kernel writes the quantized row + its scale.

The kernels are COMPILED for the TPU unless the caller passes
`interpret=True` (the CPU tests do); nothing here looks at the backend.
`tests/test_aot_tpu_compile.py` lowers every variant for a described
v5e chip at serving widths, so a block shape Mosaic refuses fails
tier-1 instead of the first chip run.
"""

from __future__ import annotations

import functools
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


# ----------------------------------------------------------------------
# append kernel: one KV row into each sequence's tail block, in place
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=32)
def _build_append(L, NB, BS, KV, HD, B, W, pool_dtype, new_dtype,
                  quantized, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    view = W * BS  # positions the W-wide table can address

    def pool_map(b, layer_ref, tables_ref, pos_ref):
        # tail block of row b; clamped so an overshooting finished row
        # (pos past its own allocation) indexes table PADDING (the
        # scratch block) instead of reading out of bounds
        w = jnp.minimum(pos_ref[b] // BS, W - 1)
        return (layer_ref[0], tables_ref[b, w], 0, 0, 0)

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
        def kernel(layer_ref, tables_ref, pos_ref, kp_ref, vp_ref,
                   kn_ref, vn_ref, kp_out, vp_out):
            b = pl.program_id(0)
            p_b = pos_ref[b]
            off = p_b % BS
            kp_out[...] = kp_ref[...]
            vp_out[...] = vp_ref[...]

            @pl.when(p_b < view)
            def _write():
                kp_out[pl.ds(off, 1)] = kn_ref[...].reshape(1, KV, HD)
                vp_out[pl.ds(off, 1)] = vn_ref[...].reshape(1, KV, HD)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, None, BS, KV, HD), pool_map),
                pl.BlockSpec((None, None, BS, KV, HD), pool_map),
                pl.BlockSpec((None, KV, HD), row_map),
                pl.BlockSpec((None, KV, HD), row_map),
            ],
            out_specs=[
                pl.BlockSpec((None, None, BS, KV, HD), pool_map),
                pl.BlockSpec((None, None, BS, KV, HD), pool_map),
            ],
        )
        out_shape = [
            jax.ShapeDtypeStruct((L, NB, BS, KV, HD), pool_dtype),
            jax.ShapeDtypeStruct((L, NB, BS, KV, HD), pool_dtype),
        ]
        aliases = {3: 0, 4: 1}

    return pl.pallas_call(
        kernel,
        name="paged_kv_append",
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            # two idle rows can share the scratch tail block: the grid
            # must stay sequential so their copy-through writes don't race
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )


def paged_kv_append(k_pool, v_pool, k_new, v_new, tables, pos, layer, *,
                    k_scale=None, v_scale=None, k_new_scale=None,
                    v_new_scale=None, interpret: bool = False):
    """Write each row's new KV into its tail pool block, in place.

    k_pool/v_pool [L, NB, BS, KV, hd]; k_new/v_new [B, KV, hd] (pool
    dtype); tables [B, W] int32; pos [B] int32 (the position being
    written); layer: scalar int32 (traced OK).  With the int8 sidecar
    (`k_scale`/`v_scale` [L, NB, BS, KV] f32 + per-row `k_new_scale`/
    `v_new_scale` [B, KV]) returns (k_pool, v_pool, k_scale, v_scale),
    else (k_pool, v_pool)."""
    L, NB, BS, KV, HD = k_pool.shape
    B, W = tables.shape
    quantized = k_scale is not None
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


# ----------------------------------------------------------------------
# decode attention kernel: split-KV walk over the block table
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=32)
def _build_attention(L, NB, BS, KV, HD, B, W, H, pool_dtype, q_dtype,
                     quantized, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    group = H // KV
    scale = HD ** -0.5
    q_dt = jnp.dtype(q_dtype)

    def pool_map(b, w, layer_ref, tables_ref, pos_ref):
        return (layer_ref[0], tables_ref[b, w], 0, 0, 0)

    def scale_map(b, w, layer_ref, tables_ref, pos_ref):
        return (layer_ref[0], tables_ref[b, w], 0, 0)

    def q_map(b, w, *_refs):
        return (b, 0, 0)

    def kernel(layer_ref, tables_ref, pos_ref, q_ref, k_ref, v_ref,
               *rest):
        if quantized:
            ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
        else:
            o_ref, m_ref, l_ref, acc_ref = rest
        b = pl.program_id(0)
        w = pl.program_id(1)
        n_w = pl.num_programs(1)
        p_b = pos_ref[b]

        @pl.when(w == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(w * BS <= p_b)
        def _compute():
            cols = w * BS + jax.lax.broadcasted_iota(
                jnp.int32, (group, BS), 1
            )
            valid = cols <= p_b
            # unrolled kv-head loop: 2-D MXU dots only (batched
            # dot_general does not lower on TPU Pallas); KV is small
            for h in range(KV):
                g0 = h * group
                if quantized:
                    kh = (k_ref[:, h, :].astype(jnp.float32)
                          * ks_ref[:, h][:, None]).astype(q_dt)
                    vh = (v_ref[:, h, :].astype(jnp.float32)
                          * vs_ref[:, h][:, None]).astype(q_dt)
                else:
                    kh = k_ref[:, h, :]
                    vh = v_ref[:, h, :]
                s = jax.lax.dot_general(
                    q_ref[g0:g0 + group, :], kh,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale
                s = jnp.where(valid, s, _NEG_INF)
                m = m_ref[g0:g0 + group]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                corr = jnp.exp(m - m_new)
                p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
                m_ref[g0:g0 + group] = m_new
                l_ref[g0:g0 + group] = (
                    l_ref[g0:g0 + group] * corr + jnp.sum(p, axis=-1)
                )
                # softmax weights cast to the compute dtype for the
                # value matmul, f32 accumulation — decode_step_vec form
                acc_ref[g0:g0 + group, :] = (
                    acc_ref[g0:g0 + group, :] * corr[:, None]
                    + jax.lax.dot_general(
                        p.astype(q_dt), vh,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )

        @pl.when(w == n_w - 1)
        def _finalize():
            l = l_ref[...]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[...] = (acc_ref[...] / safe_l[:, None]).astype(
                o_ref.dtype
            )

    in_specs = [
        pl.BlockSpec((None, H, HD), q_map),
        pl.BlockSpec((None, None, BS, KV, HD), pool_map),
        pl.BlockSpec((None, None, BS, KV, HD), pool_map),
    ]
    if quantized:
        in_specs += [
            pl.BlockSpec((None, None, BS, KV), scale_map),
            pl.BlockSpec((None, None, BS, KV), scale_map),
        ]

    return pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, W),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, H, HD), q_map),
            scratch_shapes=[
                pltpu.VMEM((H,), jnp.float32),
                pltpu.VMEM((H,), jnp.float32),
                pltpu.VMEM((H, HD), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, HD), q_dt),
        compiler_params=pltpu.CompilerParams(
            # rows are independent; the block walk carries the online
            # softmax scratch and must stay sequential
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )


def paged_decode_attention(q, k_pool, v_pool, tables, pos, layer, *,
                           k_scale=None, v_scale=None,
                           interpret: bool = False):
    """One step of decode attention straight off the paged pool.

    q [B, H, hd] (post-RoPE, current positions); k_pool/v_pool
    [L, NB, BS, KV, hd]; tables [B, W] int32 block tables (pad with the
    scratch block); pos [B] int32 per-row positions — attention covers
    columns 0..pos[b] inclusive, so the current row must already be
    written (`paged_kv_append` first).  `layer` scalar int32 selects
    the pool layer.  GQA: query head h attends through kv head
    h // (H // KV).  Returns o [B, H, hd] in q's dtype."""
    L, NB, BS, KV, HD = k_pool.shape
    B, W = tables.shape
    H = q.shape[1]
    quantized = k_scale is not None
    fn = _build_attention(L, NB, BS, KV, HD, B, W, H,
                          jnp.dtype(k_pool.dtype).name,
                          jnp.dtype(q.dtype).name, quantized,
                          bool(interpret))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if quantized:
        return fn(layer, tables, pos, q, k_pool, v_pool, k_scale, v_scale)
    return fn(layer, tables, pos, q, k_pool, v_pool)
