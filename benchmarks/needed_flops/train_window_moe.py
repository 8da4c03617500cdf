"""The `afmoe` family TRAINED, one chip's share: the operations a
token's forward AND backward need (6 a matmul weight it passes through:
2 forward, 4 backward; attention's score and value products three times
their forward, a (query, key) pair at each position's own context), no
recomputation counted.

Window layers see `min(i + 1, sliding_window)` keys, full layers `i +
1`; heads are grouped, which changes the bytes and not the operations.
An expert layer is the router over `deployment.router_experts`, the
shared expert, and the experts a token's top-k find ON THIS CHIP by
expectation: `num_experts_per_tok * num_experts (held) / router_experts`
(one, at 8 x 16 / 128).  The head is the slice's `vocab_size` rows; the
embedding is a lookup and counts nothing."""

from __future__ import annotations

from benchmarks.needed_flops import _common as c

SLIDING = "sliding_attention"


def reached(config: dict) -> float:
    """Held experts a token reaches by expectation, one expert layer."""
    m = config["model"]
    return (m["num_experts_per_tok"] * m["num_experts"]
            / config["deployment"]["router_experts"])


def matmul_weights(config: dict) -> dict:
    """Matmul weights a token passes through, by part."""
    m = config["model"]
    D, H, KV, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    L, dense = m["num_hidden_layers"], m["num_dense_layers"]
    # q, k, v, o and the output gate of the query's width
    attn = c.gqa(D, H, KV, hd) + D * H * hd
    inter = m["moe_intermediate_size"]
    expert_layer = (c.routed(D, config["deployment"]["router_experts"],
                             reached(config), inter)
                    + m["num_shared_experts"] * c.swiglu(D, inter))
    return {"attention": L * attn,
            "dense": dense * c.swiglu(D, m["intermediate_size"]),
            "experts": (L - dense) * expert_layer,
            "layers": L * attn + dense * c.swiglu(D, m["intermediate_size"])
            + (L - dense) * expert_layer,
            "head": m["vocab_size"] * D}


def attention_pairs(config: dict, seq: int) -> dict:
    """(query, key) pairs of ONE sequence of `seq` tokens a layer, by
    the layer's kind."""
    window = config["model"]["sliding_window"]
    return {"full": c.causal_pairs(0, seq),
            "window": c.capped_pairs(0, seq, window)}


def sequence_flops(config: dict, seq: int) -> float:
    """Forward and backward of one sequence of `seq` tokens."""
    m = config["model"]
    w, pairs = matmul_weights(config), attention_pairs(config, seq)
    per_pair = 3 * c.pair_flops(m["num_attention_heads"], m["head_dim"],
                                m["head_dim"])
    attn = sum(per_pair * pairs["window" if kind == SLIDING else "full"]
               for kind in m["layer_types"])
    return 6.0 * (w["layers"] + w["head"]) * seq + attn


def token_flops(config: dict, mix: dict) -> float:
    """The FLOPs a token of the mix's sequences needs."""
    seq = int(mix["seq"])
    return sequence_flops(config, seq) / seq


def request_flops(config: dict, mix: dict, prompt_len: int, got: int,
                  fields: dict) -> float:
    """The planes' common name for it: a `request` of a train stream is
    one sequence of `prompt_len` tokens (nothing is generated)."""
    return sequence_flops(config, int(prompt_len))
