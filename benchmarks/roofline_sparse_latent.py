"""Operations and bytes of the selecting latent attention, the window
latent attention and a SHARE of an expert layer, from their shapes (as
`roofline.py` counts the others: what the mathematics needs, so a
share can only be overstated by a program that does less).
"""

from __future__ import annotations

from benchmarks.roofline import least_seconds, share  # noqa: F401
from benchmarks.roofline_latent_moe import mla_decode, moe_routed


def dsa_index(live_tokens: float, rows: float, index_heads: int,
              index_dim: int, cache_bytes: int = 2) -> dict:
    """One decode step of the indexer, one full layer: every live
    token's index key (`index_dim` values) is read ONCE and scored by
    all `index_heads` index queries of its row, 2 * index_dim
    multiply-adds each.  Bytes: the keys, the `rows` live rows' queries
    `[index_heads, index_dim]` and weights read, one float32 score a
    live token written."""
    return {"flops": 2 * index_heads * index_dim * live_tokens,
            "bytes": (live_tokens * index_dim * cache_bytes
                      + rows * index_heads * (index_dim + 1) * 2
                      + live_tokens * 4)}


def dsa_sparse_decode(selected: float, rows: float, heads: int, latent: int,
                      value: int, cache_bytes: int = 2) -> dict:
    """One decode step of absorbed latent attention over the SELECTED
    rows alone, one full layer: `selected` = the live rows' `min(T,
    index_topk)` summed; the count is `mla_decode`'s at that many
    tokens (each selected latent row read once for all heads)."""
    return mla_decode(selected, rows, heads, latent, value, cache_bytes)


def swa_decode(window_rows: float, rows: float, heads: int, latent: int,
               value: int, cache_bytes: int = 2) -> dict:
    """One decode step of absorbed latent attention over a window, one
    window layer: `window_rows` = the live rows' `min(T, window)`
    summed, at the window layers' own widths."""
    return mla_decode(window_rows, rows, heads, latent, value, cache_bytes)


def ep_moe_routed(rows: float, top_k: int, held: int, router_experts: int,
                  experts_touched: float, layers: int, dim: int,
                  inter: int) -> dict:
    """The HELD experts of one decode step over `layers` expert layers:
    of the `rows * top_k` (token, expert) pairs a layer the share that
    falls to this chip's `held` of `router_experts` experts (the
    expected `held / router_experts` of them), and `experts_touched`
    distinct (layer, held expert) pairs, each expert's three matrices
    read once: `moe_routed`'s count at those pairs."""
    pairs = rows * top_k * held / router_experts
    return moe_routed(pairs, experts_touched, layers, dim, inter)
