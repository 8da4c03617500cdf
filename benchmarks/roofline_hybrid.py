"""Operations and bytes of the hybrid decoder's kernels, from their
shapes (as `roofline.py` counts the others: what the mathematics needs,
so a share can only be overstated by a kernel that does less).  Both of
its kernels are shared ones at new shapes, so both counts are the shared
ones: the paged decode attention at 32 / 8 heads of 64 over ONE
attention layer's live K and V rows (`roofline.paged_decode`), and the
routed experts at 2048 x 1792 (`roofline_latent_moe.moe_routed`: one
expert's three matrices are 11.01M weights, 22.0 MB in bfloat16).  The
gated short convolution is no kernel: three taps on rows the projections
already hold, under the scope `short_conv`.
"""

from __future__ import annotations

from benchmarks.roofline import least_seconds, paged_decode, share  # noqa: F401
from benchmarks.roofline_latent_moe import moe_routed  # noqa: F401


def cache_bytes(live_tokens: float, live_rows: float, bytes_per_token: int,
                bytes_per_slot: int) -> float:
    """What the two cache kinds hold together for the live batch: the
    paged K and V of every live token and one state a live row."""
    return live_tokens * bytes_per_token + live_rows * bytes_per_slot
