"""MiMo-V2: grouped-query attention whose keys are wider than its
values, FULL or WINDOW a layer by `hybrid_layer_pattern` (a window of
`sliding_window` keys that counts the token itself, at heads of its
own; its sink is one more column of the softmax and counts nothing), a
dense SwiGLU or a SHARE of an expert layer by `moe_layer_freq`
(`deployment`: this chip holds `n_routed_experts` of the router's
`router_experts`)."""

from __future__ import annotations

from benchmarks.needed_flops import _common as c
from benchmarks.needed_flops import serve_latent_moe as _latent

FULL = 0


def _widths(m: dict, kind: int) -> tuple:
    """(heads, KV heads, key width, value width) of a layer's kind."""
    p = "" if kind == FULL else "swa_"
    return (m[p + "num_attention_heads"], m[p + "num_key_value_heads"],
            m[p + "head_dim"], m[p + "v_head_dim"])


def matmul_weights(config: dict) -> dict:
    m = config["model"]
    D, router = m["hidden_size"], config["deployment"]["router_experts"]
    total = 0.0
    for kind, experts in zip(m["hybrid_layer_pattern"], m["moe_layer_freq"]):
        H, KV, dk, dv = _widths(m, kind)
        total += D * (H * dk + KV * (dk + dv)) + H * dv * D
        total += (_latent.expert_layer(m, router) if experts
                  else c.swiglu(D, m["intermediate_size"]))
    return {"layers": total, "head": m["vocab_size"] * D}


def attention_flops(m: dict, lo: int, hi: int) -> float:
    total = 0.0
    for kind in m["hybrid_layer_pattern"]:
        H, _, dk, dv = _widths(m, kind)
        total += c.pair_flops(H, dk, dv) * (
            c.causal_pairs(lo, hi) if kind == FULL
            else c.capped_pairs(lo, hi, m["sliding_window"]))
    return total


def request_flops(config: dict, mix: dict, prompt_len: int, got: int,
                  fields: dict) -> float:
    m = config["model"]
    return c.one_token_request(
        matmul_weights(config), lambda lo, hi: attention_flops(m, lo, hi),
        mix, prompt_len, got)
