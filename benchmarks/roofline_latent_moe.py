"""Operations and bytes of the latent-attention and expert kernels, from
their shapes (as `roofline.py` counts the others: what the mathematics
needs, so a share can only be overstated by a kernel that does less).
"""

from __future__ import annotations

from benchmarks.roofline import least_seconds, share  # noqa: F401


def mla_decode(live_tokens: float, batch: int, heads: int, latent: int,
               value: int, cache_bytes: int = 2) -> dict:
    """One decode step of ABSORBED latent attention over a paged latent
    cache, one layer: every live token's row (`latent` = compressed KV
    + rotary key values; a pad to the device's tiling is not counted)
    is read ONCE and serves all heads as key and as value; q.row is
    2 * latent and p.row 2 * value multiply-adds per (head, live
    token).  Bytes: the rows, the queries [batch, heads, latent] read
    and the results [batch, heads, value] written."""
    return {"flops": 2 * heads * (latent + value) * live_tokens,
            "bytes": (live_tokens * latent * cache_bytes
                      + batch * heads * (latent + value) * 2)}


def moe_routed(pairs: int, experts_touched: float, layers: int, dim: int,
               inter: int, weight_bytes: int = 2) -> dict:
    """The routed experts of one decode step over `layers` expert
    layers: `pairs` (token, expert) rows a layer, each through one
    SwiGLU expert (gate, up, down: 3 * dim * inter weights, 2 flops
    each); `experts_touched` distinct (layer, expert) pairs summed over
    the layers, each expert's three matrices read once.  Bytes: those
    weights, plus the rows in and out (the sorted copy of the hidden
    states read three times at width dim / inter, the result written)."""
    w = 3 * dim * inter
    rows = layers * pairs * (2 * dim + inter + dim) * 2
    return {"flops": 2 * w * pairs * layers,
            "bytes": experts_touched * w * weight_bytes + rows}
