"""Operations of a PREFILL's causal attention over a cache, from what
its launches held (as `roofline.py` counts the others: what the
mathematics needs, so a share can only be overstated by a program that
does less).
"""

from __future__ import annotations

from benchmarks.roofline import share  # noqa: F401


def causal_prefill(attended_pairs: int, heads: int, k_dim: int, v_dim: int,
                   layers: int = 1) -> dict:
    """Attention of query rows that each start behind `lo` cached keys:
    `attended_pairs` is the (query, key) pairs the causal mask leaves,
    `n * lo + n * (n + 1) / 2` a part of `n` rows, summed by the engine
    over what its launches held.  q.k is `2 * k_dim` and p.v `2 * v_dim`
    operations a (query head, pair), in each of `layers`.  Compute-bound
    at any length a prefill has: bytes are not counted."""
    return {"flops": 2 * heads * (k_dim + v_dim) * attended_pairs * layers,
            "bytes": 0}
