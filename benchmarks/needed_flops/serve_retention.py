"""Power retention (Brumby): the Qwen3 block with its softmax attention
replaced by retention of degree `assumed.degree` under a log-gate a KV
head, a SwiGLU, an untied head.

Retention has two forms of one result, and a request is counted at the
cheaper.  RECURRENT: a state of `monomials x (d + 1)` values a KV head;
a position decays and updates it (3 operations a value) and every query
head reads it (2 a value), whatever the context: 110.8 M operations a
position and layer here.  PAIRS (the chunked scan with the request one
chunk: no state is carried, none written): the score `q . k` (`2 d`), its
power, and the weighted value (`2 d`) a (query head, key) pair over the
causal context, as `reference/brumby.py` computes it: 20,480 a pair and
layer here, the cheaper up to ~10.8k positions a request, so at every
length this plane's mixes send.
"""

from __future__ import annotations

import math

from benchmarks.needed_flops import _common as c


def matmul_weights(config: dict) -> dict:
    m = config["model"]
    D, d = m["hidden_size"], m["head_dim"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    attn = c.gqa(D, H, KV, d) + D * KV        # and the gate a KV head
    return {"layers": m["num_hidden_layers"]
            * (attn + c.swiglu(D, m["intermediate_size"])),
            "head": m["vocab_size"] * D}


def retention_flops(config: dict, lo: int, hi: int) -> float:
    """One layer's retention over the positions `lo .. hi - 1`."""
    m = config["model"]
    d, H, KV = (m["head_dim"], m["num_attention_heads"],
                m["num_key_value_heads"])
    degree = int(config["assumed"]["degree"])
    state = math.comb(d + degree - 1, degree) * (d + 1)
    recurrent = (hi - lo) * state * (3 * KV + 2 * H)
    return min(recurrent, c.pair_flops(H, d, d) * c.causal_pairs(lo, hi))


def request_flops(config: dict, mix: dict, prompt_len: int, got: int,
                  fields: dict) -> float:
    L = config["model"]["num_hidden_layers"]
    return c.one_token_request(
        matmul_weights(config),
        lambda lo, hi: L * retention_flops(config, lo, hi),
        mix, prompt_len, got)
