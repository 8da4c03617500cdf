"""Arithmetic the planes' counts share.  Counted as `roofline.py`
counts: what the mathematics needs, so a share can only be overstated
by a program that does less.

A request of `prompt_len` tokens answered by `got`: the positions `lo ..
hi - 1` pass through the layers (`span`), the prompt behind whatever
prefix the mix keeps resident and every answer token but the last,
which is fed to nothing; `got` positions need their logits (the
prompt's last and every answer token's but the last).  A position is 2
operations a multiply-add for every matmul weight it passes through;
the embedding is a lookup and counts nothing.  Attention is counted a
(query, key) pair at each position's own context.
"""

from __future__ import annotations


def resident(mix: dict, prompt_len: int) -> int:
    """The prompt's leading tokens that no request needs computed: the
    shared prefix the mix keeps in this prompt (`loadgen._prompts`'s
    `keep`).  Such a prefix is CONTEXT, its keys attended and indexed;
    it is computed once a group, in set-up where the plane makes it
    resident, and is credited to no request."""
    sp = mix.get("shared_prefix") or {}
    if not int(sp.get("groups", 0)):
        return 0
    return min(int(sp["len"]),
               max(0, int(prompt_len) - int(sp.get("min_suffix", 16))))


def span(mix: dict, prompt_len: int, got: int) -> tuple:
    """(lo, hi): positions `lo .. hi - 1` pass through the layers."""
    return resident(mix, prompt_len), int(prompt_len) + int(got) - 1


def causal_pairs(lo: int, hi: int) -> float:
    """(query, key) pairs of the queries at `lo .. hi - 1`, each over
    every position up to its own: the sum of `p + 1`."""
    return (hi * (hi + 1) - lo * (lo + 1)) / 2.0


def capped_pairs(lo: int, hi: int, cap: int) -> float:
    """The same with a query seeing `min(p + 1, cap)` keys: a window
    that counts the token itself, or a selection of `cap` keys."""
    k = min(max(int(cap), lo), hi)
    return causal_pairs(lo, k) + (hi - k) * float(cap)


def swiglu(dim: int, inter: int) -> int:
    """Weights of one gated MLP: gate, up, down."""
    return 3 * dim * inter


def gqa(dim: int, heads: int, kv_heads: int, head_dim: int) -> int:
    """Weights of grouped-query attention from separate projections:
    q, k, v and o."""
    return dim * (heads + 2 * kv_heads) * head_dim + heads * head_dim * dim


def routed(dim: int, router_experts: int, reached: float, inter: int) -> float:
    """Weights of a routed expert layer a position passes through: the
    router over `router_experts` and the `reached` experts its top-k
    find on this chip."""
    return dim * router_experts + reached * swiglu(dim, inter)


def pair_flops(heads: int, k_dim: int, v_dim: int) -> int:
    """Attention's operations a (query, key) pair, one layer: the score
    `2 * k_dim` and the weighted value `2 * v_dim` a query head."""
    return 2 * heads * (k_dim + v_dim)


def one_token_request(weights: dict, pairs, mix: dict, prompt_len: int,
                      got: int) -> float:
    """A request of a model that yields one token a forward: `weights`
    = the plane's `matmul_weights`, `pairs(lo, hi)` = its attention's
    operations over the span."""
    lo, hi = span(mix, prompt_len, got)
    return (2.0 * weights["layers"] * (hi - lo) + 2.0 * weights["head"] * got
            + pairs(lo, hi))
