"""dots3-note: latent attention of two forms by `layer_types` (a FULL
layer selects `index_topk` keys by a learned indexer, a WINDOW layer sees
`sliding_window_size` keys at widths of its own), a head-wise gate, one
leading dense layer, then a SHARE of an expert layer with a shared
expert (`deployment`: this chip holds `n_routed_experts` of the
router's `router_experts`).

A full layer's indexer scores EVERY key up to the query's own (`2 x
index_head_dim` a (index head, pair)); its attention then runs over
`min(context, index_topk)` keys.  Latent attention is counted in its
expanded form, the cheaper a pair (`serve_latent_moe.py` says why).
"""

from __future__ import annotations

from benchmarks.needed_flops import _common as c
from benchmarks.needed_flops import serve_latent_moe as _latent

FULL = "full_attention"


def _prefix(kind: str) -> str:
    return "" if kind == FULL else "swa_"


def _attention(m: dict, kind: str) -> int:
    """A layer's attention weights: the latent projections, the gate,
    and in a full layer the indexer's three projections."""
    D, p = m["hidden_size"], _prefix(kind)
    w = _latent.latent_attention(m, p) + D * m[p + "num_attention_heads"]
    if kind == FULL:
        Hi, di = m["index_n_heads"], m["index_head_dim"]
        w += m["q_lora_rank"] * Hi * di + D * di + D * Hi
    return w


def matmul_weights(config: dict) -> dict:
    m = config["model"]
    D, L, dense = (m["hidden_size"], m["num_hidden_layers"],
                   m["first_k_dense_replace"])
    router = config["deployment"]["router_experts"]
    return {"layers": sum(_attention(m, k) for k in m["layer_types"])
            + dense * c.swiglu(D, m["intermediate_size"])
            + (L - dense) * _latent.expert_layer(m, router),
            "head": m["vocab_size"] * D}


def attention_flops(m: dict, lo: int, hi: int) -> float:
    """Every layer's index scores and attention over `lo .. hi - 1`."""
    total = 0.0
    for kind in m["layer_types"]:
        p = _prefix(kind)
        pair = c.pair_flops(
            m[p + "num_attention_heads"],
            m[p + "qk_nope_head_dim"] + m[p + "qk_rope_head_dim"],
            m[p + "v_head_dim"])
        if kind == FULL:
            total += (2 * m["index_n_heads"] * m["index_head_dim"]
                      * c.causal_pairs(lo, hi)
                      + pair * c.capped_pairs(lo, hi, m["index_topk"]))
        else:
            total += pair * c.capped_pairs(lo, hi, m["sliding_window_size"])
    return total


def request_flops(config: dict, mix: dict, prompt_len: int, got: int,
                  fields: dict) -> float:
    m = config["model"]
    return c.one_token_request(
        matmul_weights(config), lambda lo, hi: attention_flops(m, lo, hi),
        mix, prompt_len, got)
