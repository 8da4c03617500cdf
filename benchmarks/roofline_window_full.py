"""Operations and bytes of grouped-query attention whose keys and values
differ in width (192 / 128), over a paged cache in the full layers and
over a per-slot ring of window rows in the window layers, and of a SHARE
of an expert layer, from their shapes (as `roofline.py` counts the
others: what the mathematics needs, so a share can only be overstated
by a program that does less).
"""

from __future__ import annotations

from benchmarks.roofline import least_seconds, share  # noqa: F401
from benchmarks.roofline_hybrid import cache_bytes  # noqa: F401
from benchmarks.roofline_sparse_latent import ep_moe_routed  # noqa: F401


def gqa_decode(tokens: float, rows: float, heads: int, kv_heads: int,
               k_dim: int, v_dim: int, cache_bytes: int = 2) -> dict:
    """One decode step of grouped-query attention over `tokens` cached
    tokens, one layer: every token's K row (`kv_heads * k_dim` values)
    and V row (`kv_heads * v_dim`) is read ONCE and serves its group's
    query heads; q.k is 2 * k_dim and p.v 2 * v_dim multiply-adds per
    (query head, token).  Bytes: those rows, the `rows` live rows'
    queries `[heads, k_dim]` read and results `[heads, v_dim]` written.
    A full layer: `tokens` = the live tokens (2,560 B each at 4 heads of
    192 + 128); a window layer: `tokens` = the live rows' `min(T,
    window)` summed (5,120 B each at 8 heads)."""
    return {"flops": 2 * heads * (k_dim + v_dim) * tokens,
            "bytes": (tokens * kv_heads * (k_dim + v_dim) * cache_bytes
                      + rows * heads * (k_dim + v_dim) * 2)}
