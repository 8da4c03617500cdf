"""Llama-lineage dense decoder (Mistral): grouped-query attention from
separate projections, a SwiGLU, an untied head; full causal attention
(a configuration with a `sliding_window` is refused: the plane serves
none)."""

from __future__ import annotations

from benchmarks.needed_flops import _common as c


def matmul_weights(config: dict) -> dict:
    """Matmul weights ONE position passes through: `layers` in the
    layers, `head` where its logits are needed."""
    m = config["model"]
    D, d = m["hidden_size"], m["head_dim"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    return {"layers": m["num_hidden_layers"]
            * (c.gqa(D, H, KV, d) + c.swiglu(D, m["intermediate_size"])),
            "head": m["vocab_size"] * D}


def request_flops(config: dict, mix: dict, prompt_len: int, got: int,
                  fields: dict) -> float:
    m = config["model"]
    a_pair = m["num_hidden_layers"] * c.pair_flops(
        m["num_attention_heads"], m["head_dim"], m["head_dim"])
    assert not m.get("sliding_window"), "counted as full causal attention"
    return c.one_token_request(
        matmul_weights(config), lambda lo, hi: a_pair * c.causal_pairs(lo, hi),
        mix, prompt_len, got)
