"""LFM2-MoE: gated short convolutions and grouped-query attention layers
by `layer_types`, leading dense SwiGLUs and then routed experts, the
embedding tied as the head.  A convolution layer is its two projections
and `conv_L_cache` taps a channel (2 operations a tap: counted with the
weights); its state is the last taps' inputs, so it costs the same at
any context."""

from __future__ import annotations

from benchmarks.needed_flops import _common as c


def _attention_layers(m: dict) -> int:
    return sum(t != "conv" for t in m["layer_types"])


def matmul_weights(config: dict) -> dict:
    m, d = config["model"], config["assumed"]["head_dim"]
    D, H, KV = (m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"])
    L, dense, attn = (m["num_hidden_layers"], m["num_dense_layers"],
                      _attention_layers(m))
    conv = D * 3 * D + D * m["conv_L_cache"] + D * D   # in, taps, out
    experts = c.routed(D, m["num_experts"], m["num_experts_per_tok"],
                       m["moe_intermediate_size"])
    return {"layers": (L - attn) * conv + attn * c.gqa(D, H, KV, d)
            + dense * c.swiglu(D, m["intermediate_size"])
            + (L - dense) * experts,
            "head": m["vocab_size"] * D}


def request_flops(config: dict, mix: dict, prompt_len: int, got: int,
                  fields: dict) -> float:
    m, d = config["model"], config["assumed"]["head_dim"]
    a_pair = _attention_layers(m) * c.pair_flops(
        m["num_attention_heads"], d, d)
    return c.one_token_request(
        matmul_weights(config), lambda lo, hi: a_pair * c.causal_pairs(lo, hi),
        mix, prompt_len, got)
