"""DeepSeek-V3 lineage (kanana-2): multi-head latent attention, leading
dense layers, then expert layers with shared experts.

Latent attention has two forms of one result.  Both cost a position 2
operations a weight of `wkv_b` (expanded: its own keys and values;
absorbed: its query and its output through the same matrix), so the
matmul part is the same.  A (query, key) pair costs `2 H (qk_head +
v_head)` expanded and `2 H (2 rank + rope)` absorbed, 320 against 1,088
a head here: the EXPANDED form is the cheaper and is what is counted.
"""

from __future__ import annotations

from benchmarks.needed_flops import _common as c


def latent_attention(m: dict, p: str = "") -> int:
    """Weights of one latent-attention layer at the widths under the
    prefix `p`; with a query rank the query goes through two matrices."""
    D, H = m["hidden_size"], m[p + "num_attention_heads"]
    nope, rope, v = (m[p + "qk_nope_head_dim"], m[p + "qk_rope_head_dim"],
                     m[p + "v_head_dim"])
    r, qr = m[p + "kv_lora_rank"], m.get(p + "q_lora_rank")
    q = (D * qr + qr * H * (nope + rope)) if qr else D * H * (nope + rope)
    return q + D * (r + rope) + r * H * (nope + v) + H * v * D


def expert_layer(m: dict, router_experts: int = 0) -> float:
    """Router, the routed experts and the shared experts.  A position's
    top-k reach `num_experts_per_tok` experts; where the chip HOLDS
    `n_routed_experts` of the router's `router_experts` (a deployment's
    share) a pair routed elsewhere goes to no expert here, and the
    expected `held / router_experts` of the pairs are counted, as
    `roofline_sparse_latent.ep_moe_routed` counts them."""
    D, Im = m["hidden_size"], m["moe_intermediate_size"]
    held = m["n_routed_experts"]
    router = router_experts or held
    return (c.routed(D, router, m["num_experts_per_tok"] * held / router, Im)
            + c.swiglu(D, (m.get("n_shared_experts") or 0) * Im))


def matmul_weights(config: dict) -> dict:
    m = config["model"]
    D, L, dense = (m["hidden_size"], m["num_hidden_layers"],
                   m["first_k_dense_replace"])
    return {"layers": L * latent_attention(m)
            + dense * c.swiglu(D, m["intermediate_size"])
            + (L - dense) * expert_layer(m),
            "head": m["vocab_size"] * D}


def request_flops(config: dict, mix: dict, prompt_len: int, got: int,
                  fields: dict) -> float:
    m = config["model"]
    a_pair = m["num_hidden_layers"] * c.pair_flops(
        m["num_attention_heads"],
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"])
    return c.one_token_request(
        matmul_weights(config), lambda lo, hi: a_pair * c.causal_pairs(lo, hi),
        mix, prompt_len, got)
