"""Operations and bytes a kernel's algorithm needs for one call, from
its shapes, and the least time a chip could take for them.  Part of the
yardstick.  Recomputed work does not count: the numbers are what the
mathematics needs, so a share of the roofline can only be overstated
by a kernel that does less than this, never by one that does more.
"""

from __future__ import annotations


def flash_fwd(batch: int, heads: int, seq: int, head_dim: int,
              dtype_bytes: int = 2, causal: bool = True) -> dict:
    """Attention forward: QK^T and PV, 2*T*T*hd multiply-adds each per
    head, half of them under a causal mask.  Bytes: Q, K, V read and O
    written once."""
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    return {"flops": 4 * batch * heads * pairs * head_dim,
            "bytes": 4 * batch * heads * seq * head_dim * dtype_bytes}


def flash_bwd(batch: int, heads: int, seq: int, head_dim: int,
              dtype_bytes: int = 2, causal: bool = True) -> dict:
    """Attention backward: dV = P^T dO, dP = dO V^T, dQ = dS K,
    dK = dS^T Q, and the scores S = QK^T once more (needed to form P;
    counted, because no attention backward can avoid it without storing
    the T*T matrix).  Five products of 2*T*T*hd.  Bytes: Q, K, V, O, dO
    read, dQ, dK, dV written."""
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    return {"flops": 10 * batch * heads * pairs * head_dim,
            "bytes": 8 * batch * heads * seq * head_dim * dtype_bytes}


def paged_decode(live_tokens: int, batch: int, heads: int, kv_heads: int,
                 head_dim: int, kv_bytes: int = 2) -> dict:
    """One decode step of attention over a paged cache, one layer:
    every live token's K and V row is read once; q.k and p.v are
    2*hd multiply-adds per (query head, live token) each."""
    return {"flops": 4 * heads * head_dim * live_tokens,
            "bytes": (2 * live_tokens * kv_heads * head_dim * kv_bytes
                      + 2 * batch * heads * head_dim * 2)}


def least_seconds(work: dict, peaks: dict) -> dict:
    t_c = work["flops"] / peaks["bf16_flops_per_s"]
    t_m = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}


def share(work: dict, measured_s: float, peaks: dict):
    """Percent of the roofline reached; None without a measurement."""
    if not measured_s or measured_s <= 0:
        return None
    return 100.0 * least_seconds(work, peaks)["seconds"] / measured_s


def dense_train_flops_per_token(n_matmul_params: int, n_layer: int, seq: int,
                                width: int, causal: bool = True) -> float:
    """Forward and backward of a dense decoder, per token: 6 per
    parameter that sits in a matmul (the tied head counts, the position
    table does not), plus attention's score and value products,
    12 * L * T * d over a full context (Kaplan et al. 2020, table 1) and
    half of that under a causal mask, where the other half is never
    needed.  Recomputation in the backward pass is not counted."""
    attn = 12.0 * n_layer * seq * width
    return 6.0 * n_matmul_params + (attn / 2 if causal else attn)
