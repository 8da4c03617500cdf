"""Operations and bytes of Mamba-2's recurrence, in admission and in a
decode step, and of a SHARE of an expert layer whose experts are two
matrices in a latent width, from their shapes (as `roofline.py` counts
the others: what the mathematics needs, the same work whatever computes
it, so a share can only be overstated by a program that does less).
The recurrence is counted in its RECURRENT form: a position's state
update `S = a S + (dt x) B^T` (three operations an element of `[heads,
head_dim, state]`) and its read `y = S C` (two)."""

from __future__ import annotations

from benchmarks.roofline import least_seconds, share  # noqa: F401


def ssm_scan(tokens: float, programs: float, heads: int, head_dim: int,
             groups: int, state: int, io_bytes: int = 2) -> dict:
    """The scan of `tokens` positions in `programs` admission programs,
    one Mamba layer: 5 operations a position and state element; bytes:
    a position's `x`, `B`, `C` read and `y` written in the model's
    dtype, `dt` float32, and a program's float32 state read and written
    once."""
    elems = heads * head_dim * state
    return {"flops": 5.0 * elems * tokens,
            "bytes": (tokens * ((2 * heads * head_dim + 2 * groups * state)
                                * io_bytes + 4 * heads)
                      + programs * 2 * 4 * elems)}


def ssm_step(rows: float, dim: int, heads: int, head_dim: int, groups: int,
             state: int, taps: int, w_bytes: int = 2) -> dict:
    """One decode step of one Mamba layer at `rows` live rows: each
    row's float32 state read and written once, its convolution state
    read and written, and the layer's two projections read once and
    multiplied by every live row."""
    di, conv = heads * head_dim, heads * head_dim + 2 * groups * state
    proj = dim * (di + conv + heads) + di * dim
    return {"flops": rows * (2.0 * proj + 5.0 * di * state
                             + 2.0 * taps * conv),
            "bytes": (rows * (2 * 4 * di * state
                              + 2 * (taps - 1) * conv * w_bytes)
                      + proj * w_bytes)}


def latent_moe_routed(rows: float, top_k: int, held: int,
                      router_experts: int, experts_touched: float,
                      layers: int, latent: int, inter: int,
                      w_bytes: int = 2) -> dict:
    """The HELD experts of one decode step over `layers` expert layers:
    of the `rows * top_k` (row, expert) pairs a layer the expected
    `held / router_experts` fall to this chip, two products of `latent x
    inter` a pair; `experts_touched` distinct (layer, held expert) pairs
    a step, each expert's TWO matrices read once; a pair's latent row
    read and written."""
    pairs = layers * rows * top_k * held / router_experts
    return {"flops": pairs * 2 * 2.0 * latent * inter,
            "bytes": (experts_touched * 2 * latent * inter * w_bytes
                      + pairs * 2 * latent * w_bytes)}
