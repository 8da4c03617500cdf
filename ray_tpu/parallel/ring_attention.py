"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

Absent from the reference (SURVEY §5.7 verified no ring/Ulysses
implementation exists there); built natively here as the long-context
strategy.  Design: q/k/v are sharded over the sequence axis; each device
keeps its Q shard resident and passes its K/V shard around the ring with
`lax.ppermute` (which XLA lowers to ICI neighbor exchanges), folding
each visiting block into a running flash-style online softmax.  Compute
on block i overlaps with the transfer of block i+1 (XLA schedules the
ppermute concurrently with the einsums since there is no data
dependency).

Also provides Ulysses-style all-to-all attention: scatter heads /
gather sequence via `lax.all_to_all`, run full-sequence attention per
head group, invert.  Ring scales to sequence lengths that don't fit a
chip; Ulysses is cheaper at moderate lengths when heads >= sp.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental.shard_map import shard_map

_NEG_INF = -1e30


def select_attention(kind: str, q, k, v, mesh=None, causal: bool = True):
    """One dispatch point for the attention backends (dense | flash |
    ring | ulysses) shared by all model families.

    Without a mesh a remat block (`ops.attention.checkpoint_block`)
    keeps the flash kernel's `out` and `lse` and its replay does not
    run the kernel again.  Under a mesh the kernel sits inside the
    `shard_map` below, its names with it, where the block's policy does
    not see them: that route recomputes the forward kernel."""
    if kind == "flash":
        from ray_tpu.ops import flash_attention

        if mesh is None:
            return flash_attention(q, k, v, causal)
        # the kernel is a custom call the SPMD partitioner cannot
        # split, so place it per shard: batch over the data axes, heads
        # over tp, every device running the kernel on its own block
        spec = P(("dp", "fsdp"), None, "tp", None)
        return shard_map(
            lambda q, k, v: flash_attention(q, k, v, causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_rep=False,
        )(q, k, v)
    if kind == "ring" and mesh is not None:
        return ring_attention(q, k, v, mesh, causal=causal)
    if kind == "ulysses" and mesh is not None:
        return ulysses_attention(q, k, v, mesh, causal=causal)
    return plain_attention(q, k, v, causal=causal)


def _block_attn(q, k, v, bias, scale):
    """One q-block x kv-block attention with streaming-softmax stats.

    Returns (unnormalized_out, row_max, row_sumexp)."""
    # q: [B, Tq, H, D], k/v: [B, Tk, H, D]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)  # [B, H, Tq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # [B, H, Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, m, l


def _ring_attention_local(q, k, v, axis_name: str, causal: bool, scale: float):
    """Per-device body under shard_map; sequence dim is the local shard."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]

    def make_bias(kv_idx):
        if not causal:
            return None
        # global positions: rows my_idx*Tq + iq, cols kv_idx*Tk + ik
        rows = my_idx * Tq + jnp.arange(Tq)[:, None]
        cols = kv_idx * Tk + jnp.arange(Tk)[None, :]
        return jnp.where(rows >= cols, 0.0, _NEG_INF)[None, None, :, :]

    def step(carry, _):
        o_acc, m_acc, l_acc, k_cur, v_cur, step_i = carry
        kv_idx = (my_idx - step_i) % axis_size
        o_b, m_b, l_b = _block_attn(q, k_cur, v_cur, make_bias(kv_idx), scale)
        # online softmax merge (flash-attention style)
        m_new = jnp.maximum(m_acc, m_b)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_b - m_new)
        l_new = l_acc * alpha + l_b * beta
        o_new = (
            o_acc * alpha.transpose(0, 2, 1)[..., None]
            + o_b * beta.transpose(0, 2, 1)[..., None]
        )
        # rotate k/v to the next ring neighbor (ICI exchange)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt, step_i + 1), None

    o0 = jnp.zeros_like(q)
    m0 = jnp.full((B, H, Tq), _NEG_INF, dtype=q.dtype)
    l0 = jnp.zeros((B, H, Tq), dtype=q.dtype)
    (o, m, l, _, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v, jnp.int32(0)), None, length=axis_size
    )
    l = jnp.maximum(l, 1e-20)
    return o / l.transpose(0, 2, 1)[..., None]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Attention over sequence-sharded q/k/v of shape [B, T, H, D].

    T is the GLOBAL sequence length; inputs may be unsharded (the
    shard_map in/out specs place them).  Batch stays sharded over
    (dp, fsdp), heads over tp, sequence over `axis_name`.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(("dp", "fsdp"), axis_name, "tp", None)
    fn = shard_map(
        functools.partial(
            _ring_attention_local, axis_name=axis_name, causal=causal, scale=scale
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_rep=False,
    )
    return fn(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ulysses/DeepSpeed-style SP: all_to_all so each device holds the
    FULL sequence for a subset of heads, then dense attention, then the
    inverse all_to_all.  Requires H % sp == 0."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(("dp", "fsdp"), axis_name, "tp", None)

    def local(q, k, v):
        # local shapes: [b, t_local, h, d]; scatter heads, gather seq
        def a2a(x):
            return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

        def a2a_inv(x):
            return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

        qg, kg, vg = a2a(q), a2a(k), a2a(v)  # [b, T, h/sp, d]
        T = qg.shape[1]
        bias = None
        if causal:
            rows = jnp.arange(T)[:, None]
            cols = jnp.arange(T)[None, :]
            bias = jnp.where(rows >= cols, 0.0, _NEG_INF)[None, None, :, :]
        o, m, l = _block_attn(qg, kg, vg, bias, scale)
        o = o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
        return a2a_inv(o)

    fn = shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_rep=False,
    )
    return fn(q, k, v)


def plain_attention(q, k, v, *, causal=True, scale=None, mask=None):
    """Reference (unsharded) attention used in tests and as the
    single-device path.  `mask` [T, S] bool, when given, says which
    keys a query sees, in place of the causal triangle."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is None and causal:
        T, S = s.shape[-2], s.shape[-1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
