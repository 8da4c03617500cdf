"""Operations and bytes of the kernels a TRAINED share of the `afmoe`
family calls, from their shapes (as `roofline.py` counts the others:
what the mathematics needs, no recomputation, so a share can only be
overstated by a program that does less).

- flash attention with grouped heads and a window, forward and
  backward: `roofline.flash_fwd` / `flash_bwd`'s products over the
  pairs a layer's kind leaves (`min(i + 1, window)` keys a query in a
  window layer); Q, O (and dO, dQ) at the query heads, K, V (and dK,
  dV) at the KV heads, each read or written once;
- the grouped products over the held (token, expert) pairs: a pair's
  gate, up and down products forward and the same three for the input's
  gradient (`gmm`), and the three for the matrices' gradient (`tgmm`,
  which writes every held matrix's gradient, zeros for an expert with
  no pair).
"""

from __future__ import annotations

from benchmarks.roofline import least_seconds, share  # noqa: F401

SLIDING = "sliding_attention"


def pairs(seq: int, window=None) -> float:
    """(query, key) pairs of one causal sequence, a query seeing `min(i
    + 1, window)` keys."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def flash(batch: int, heads: int, kv_heads: int, seq: int, head_dim: int,
          window, products: int, q_arrays: int, kv_arrays: int,
          dtype_bytes: int = 2) -> dict:
    """`products` matrix products of 2 * pairs * head_dim a query head;
    `q_arrays` arrays of the queries' shape and `kv_arrays` of the keys'
    read or written once."""
    return {"flops": 2.0 * products * batch * heads * pairs(seq, window)
            * head_dim,
            "bytes": float(batch * seq * head_dim * dtype_bytes
                           * (q_arrays * heads + kv_arrays * kv_heads))}


def flash_fwd(batch, heads, kv_heads, seq, head_dim, window=None) -> dict:
    """QK^T and PV; Q read, O written; K, V read."""
    return flash(batch, heads, kv_heads, seq, head_dim, window, 2, 2, 2)


def flash_bwd(batch, heads, kv_heads, seq, head_dim, window=None) -> dict:
    """S again, dP, dV, dQ, dK (`roofline.flash_bwd`'s five); Q, O, dO
    read and dQ written; K, V read and dK, dV written."""
    return flash(batch, heads, kv_heads, seq, head_dim, window, 5, 4, 4)


def step_calls(model: dict, batch: int, seq: int, work) -> dict:
    """One step's calls of `work` (`flash_fwd` or `flash_bwd`) summed
    over the layers by their kind."""
    total = {"flops": 0.0, "bytes": 0.0}
    for kind in model["layer_types"]:
        w = work(batch, model["num_attention_heads"],
                 model["num_key_value_heads"], seq, model["head_dim"],
                 model["sliding_window"] if kind == SLIDING else None)
        total = {k: total[k] + w[k] for k in total}
    return total


def gmm(held_pairs: float, layers: int, held: int, dim: int, inter: int,
        dtype_bytes: int = 2) -> dict:
    """Forward and input-gradient of `held_pairs` pairs: six products
    of 2 * dim * inter a pair; a pair's rows `dim` wide read or written
    four times and `inter` wide eight.  The matrices' reads are NOT
    counted: a held expert with no pair is never read, and how many
    were touched is not among the step's counters, so counting all
    `layers * held` would overstate the need of a step that reached few
    (a share may only be understated)."""
    del layers, held
    return {"flops": 12.0 * held_pairs * dim * inter,
            "bytes": held_pairs * (4 * dim + 8 * inter) * float(dtype_bytes)}


def tgmm(held_pairs: float, layers: int, held: int, dim: int, inter: int,
         dtype_bytes: int = 2) -> dict:
    """The matrices' gradient: three products of 2 * dim * inter a
    pair; the three gradients written in float32, a pair's rows read
    (`dim` wide twice for gate and up, once for down; `inter` wide
    three times)."""
    return {"flops": 6.0 * held_pairs * dim * inter,
            "bytes": (layers * 3 * held * dim * inter * 4.0
                      + held_pairs * 3 * (dim + inter) * dtype_bytes)}
