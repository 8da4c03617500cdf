"""Nemotron-H: every layer ONE mixer by `hybrid_override_pattern`.  A
Mamba-2 layer (`M`) is its two projections and the recurrence counted
in its RECURRENT form, a position's state update and read (`5 x heads x
head_dim x state`: decay, outer product, add, and the read's
multiply-add), whatever scan computes it; an attention layer (`*`)
grouped queries over the whole causal context; an expert layer (`E`) the
router over `router_experts`, the two latent projections, the shared
expert and the EXPECTED `top_k x held / router_experts` experts a
position's picks find on this chip, TWO matrices an expert in the
latent width (`deployment`: this chip holds `n_routed_experts` of the
router's `router_experts`)."""

from __future__ import annotations

from benchmarks.needed_flops import _common as c


def _mamba(m: dict) -> int:
    """Matmul weights of a Mamba-2 layer: in_proj and out_proj."""
    D, di = m["hidden_size"], m["mamba_num_heads"] * m["mamba_head_dim"]
    gn = m["n_groups"] * m["ssm_state_size"]
    return D * (2 * di + 2 * gn + m["mamba_num_heads"]) + di * D


def _experts(m: dict, router: int) -> float:
    """Matmul weights of an expert layer a position passes through."""
    D, Z = m["hidden_size"], m["moe_latent_size"]
    reached = m["num_experts_per_tok"] * m["n_routed_experts"] / router
    return (D * router + 2 * D * Z
            + 2 * D * m["moe_shared_expert_intermediate_size"]
            + reached * 2 * Z * m["moe_intermediate_size"])


def matmul_weights(config: dict) -> dict:
    m = config["model"]
    D, router = m["hidden_size"], config["deployment"]["router_experts"]
    per = {"M": _mamba(m),
           "*": c.gqa(D, m["num_attention_heads"], m["num_key_value_heads"],
                      m["head_dim"]),
           "E": _experts(m, router)}
    return {"layers": sum(per[k] for k in m["hybrid_override_pattern"]),
            "head": m["vocab_size"] * D}


def scan_flops(m: dict) -> float:
    """The recurrence and the convolution a position, all Mamba layers."""
    di = m["mamba_num_heads"] * m["mamba_head_dim"]
    conv = di + 2 * m["n_groups"] * m["ssm_state_size"]
    return m["hybrid_override_pattern"].count("M") * (
        5.0 * di * m["ssm_state_size"] + 2.0 * m["conv_kernel"] * conv)


def request_flops(config: dict, mix: dict, prompt_len: int, got: int,
                  fields: dict) -> float:
    m = config["model"]
    attn = m["hybrid_override_pattern"].count("*") * c.pair_flops(
        m["num_attention_heads"], m["head_dim"], m["head_dim"])
    return c.one_token_request(
        matmul_weights(config),
        lambda lo, hi: (attn * c.causal_pairs(lo, hi)
                        + scan_flops(m) * (hi - lo)),
        mix, prompt_len, got)
