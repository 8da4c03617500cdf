"""Operations and bytes of the power-retention kernels, from their
shapes (as `roofline.py` counts the others: what the mathematics needs,
so a share can only be overstated by a kernel that does less).  The
state is counted at its 8,256 DISTINCT monomials a KV head (`d (d + 1)
/ 2` at `d` 128), whatever layout the program holds them in.

ONE READ A TOKEN AND ONE WRITE A CHUNK.  A token's answer needs a live
row's state READ once (`retention_read`); the state has to be WRITTEN
only where a chunk of decode steps ends (`retention_decode`: the rows
live at that step read and written once, every held token folded in).
A program that writes the state at every token calls the second alone,
at every step, and its count is the same a call.
"""

from __future__ import annotations

from benchmarks.roofline import least_seconds, share  # noqa: F401


def monomials(head_dim: int) -> int:
    """Distinct degree-2 monomials of a `head_dim`-vector."""
    return head_dim * (head_dim + 1) // 2


def state_bytes(kv_heads: int, head_dim: int) -> int:
    """One sequence's state and key sum, one layer, float32: `kv_heads`
    x (monomials x head_dim + monomials) values."""
    D = monomials(head_dim)
    return kv_heads * (D * head_dim + D) * 4


def retention_decode(live_rows: float, heads: int, kv_heads: int,
                     head_dim: int, act_bytes: int = 2) -> dict:
    """One WRITING decode step (a chunk's last, or every step of a
    program that writes a token), one layer: the state and key sum of
    every row LIVE AT THAT STEP read and written once; per row and KV
    head the decay and the `phi(k) v^T` update (3 operations a state
    value) and one read-out a query head (2 a value), the key sum
    likewise.  Bytes: the states twice, and q, k, v read and o written
    a live row."""
    D = monomials(head_dim)
    group = heads // kv_heads
    per_row = kv_heads * (D * head_dim + D) * (3 + 2 * group)
    io = (2 * heads + 2 * kv_heads) * head_dim * act_bytes
    return {"flops": live_rows * per_row,
            "bytes": live_rows * (2 * state_bytes(kv_heads, head_dim) + io)}


def retention_read(live_rows: float, heads: int, kv_heads: int,
                   head_dim: int, act_bytes: int = 2) -> dict:
    """One decode step that writes no state, one layer: every LIVE
    row's state and key sum read once, one read-out a query head (2
    operations a state value).  Bytes: the states once, and q, k, v
    read and o written a live row."""
    D = monomials(head_dim)
    per_row = kv_heads * (D * head_dim + D) * 2 * (heads // kv_heads)
    io = (2 * heads + 2 * kv_heads) * head_dim * act_bytes
    return {"flops": live_rows * per_row,
            "bytes": live_rows * (state_bytes(kv_heads, head_dim) + io)}


def retention_prefill(tokens: float, prompts: float, chunk: int, heads: int,
                      kv_heads: int, head_dim: int,
                      act_bytes: int = 2) -> dict:
    """One packed prefill, one layer, as a chunked scan with chunks of
    `chunk` tokens: `tokens` real tokens in `prompts` prompts (means
    over calls are fine: the count is linear in both).  Inside a chunk
    the causal pairs' scores and weighted values (4 * d a pair and
    query head); for every chunk but a prompt's first the read-out of
    the carried state and key sum (2 * D * (d + 1) a token and query
    head); for every chunk its keys and values into the state (2 * D *
    (d + 1) a token and KV head).  Bytes: q, k, v read and o written a
    token, and each prompt's state written once."""
    D, d = monomials(head_dim), head_dim
    chunks = tokens / chunk
    pairs = chunks * chunk * (chunk + 1) / 2
    carried = max(chunks - prompts, 0.0) * chunk
    flops = (heads * 4 * d * pairs
             + heads * 2 * D * (d + 1) * carried
             + kv_heads * 2 * D * (d + 1) * tokens)
    io = tokens * (2 * heads + 2 * kv_heads) * d * act_bytes
    return {"flops": flops,
            "bytes": io + prompts * state_bytes(kv_heads, head_dim)}
