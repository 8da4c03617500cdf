"""Operations and bytes of a block-diffusion forward's kernels, from
their shapes (as `roofline.py` counts the others: what the mathematics
needs, so a share can only be overstated by a program that does less).
A FORWARD runs `B` positions a live row: its attention reads the row's
cached K and V once for all `B x H` queries and writes the block's `B`
rows; its expert layer sees `rows x B x top_k` (token, expert) pairs.
"""

from __future__ import annotations

from benchmarks.roofline import least_seconds, share  # noqa: F401
# a forward's routed experts count as a decode step's: `pairs` = live rows
# x `B` x top-k (token, expert) rows a layer, the touched experts' three
# matrices read once
from benchmarks.roofline_latent_moe import moe_routed  # noqa: F401


def block_attention(tokens: float, rows: float, block: int, heads: int,
                    kv_heads: int, head_dim: int, cache_bytes: int = 2) -> dict:
    """One forward's attention, one layer: `rows` live rows of `block`
    query positions each over `tokens` cached tokens in all (the live
    rows' contexts, the open block's rows among them).  Every cached
    token's K and V row (`kv_heads * head_dim` values each) is read
    ONCE and serves the block's `block * heads` queries; q.k and p.v are
    `2 * head_dim` multiply-adds each per (query head, query position,
    token).  Bytes: those rows, the block's `block` K and V rows
    WRITTEN a live row, its queries read and its results written."""
    kv_row = 2 * kv_heads * head_dim * cache_bytes
    return {"flops": 4 * heads * head_dim * block * tokens,
            "bytes": (tokens * kv_row + rows * block * kv_row
                      + 2 * rows * block * heads * head_dim * 2)}
