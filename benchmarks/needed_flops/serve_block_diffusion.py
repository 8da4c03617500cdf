"""SDAR: the Qwen3-MoE decoder (grouped-query attention, every layer an
expert layer, none shared, an untied head) generating by DIFFUSION over
blocks of `assumed.block_length` positions under the block-causal mask:
a position sees every position of its own block and of the blocks
before it, and predicts ITS OWN token (no shift).

What a request of `denoising_steps` S needs (`reference/sdar.py`'s
`generate`, and `replay`'s two halves):
- the prompt's WHOLE blocks through the layers once, no head (no
  position of the prompt predicts anything: the clean half's rows);
- for every block that holds answer positions, a forward of its `B`
  positions a denoising step (the noisy half): step `s` decides
  `B // S + (s < B mod S)` of the undecided positions, so a block of `B`
  undecided positions takes S forwards.  A forward runs ALL `B`
  positions through the layers (a decided position's token changed, and
  the others attend to it), and needs the logits of the positions STILL
  UNDECIDED alone: `choose` reads no other;
- one more pass of the block's `B` CLEAN positions through the layers,
  no head, for every block but the last: the last denoising forward saw
  masks where it decided, and the next block attends to the tokens (the
  commit: `generate` counts it, the program rides it in the next block's
  first forward, nobody can leave it out).
A block that ends early because its confidences pass the threshold needs
fewer forwards than this; the client's record cannot tell, and seeded
weights over 151,936 tokens never reach a confidence of 0.9.
"""

from __future__ import annotations

from benchmarks.needed_flops import _common as c


def matmul_weights(config: dict) -> dict:
    m = config["model"]
    D, d = m["hidden_size"], m["head_dim"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    experts = c.routed(D, m["num_experts"], m["num_experts_per_tok"],
                       m["moe_intermediate_size"])
    return {"layers": m["num_hidden_layers"] * (c.gqa(D, H, KV, d) + experts),
            "head": m["vocab_size"] * D}


def denoising(undecided: int, B: int, S: int) -> tuple:
    """(forwards, logits needed) of one block that starts with
    `undecided` positions to decide."""
    forwards = logits = s = 0
    while undecided > 0:
        forwards, logits = forwards + 1, logits + undecided
        undecided -= max(1, B // S + (s < B % S))
        s += 1
    return forwards, logits


def request_flops(config: dict, mix: dict, prompt_len: int, got: int,
                  fields: dict) -> float:
    m, assumed = config["model"], config["assumed"]
    B = int(assumed["block_length"])
    S = int(fields.get("denoising_steps", assumed["denoising_steps"]))
    w = matmul_weights(config)
    a_pair = m["num_hidden_layers"] * c.pair_flops(
        m["num_attention_heads"], m["head_dim"], m["head_dim"])

    def block_pass(b):
        """Block `b`'s `B` positions through the layers once, each over
        the `(b + 1) x B` positions it sees."""
        return B * (2.0 * w["layers"] + a_pair * (b + 1) * B)

    T = int(prompt_len)
    first, last = T // B, -(-(T + int(got)) // B) - 1
    resident = c.resident(mix, T) // B
    total = sum(block_pass(b) for b in range(resident, first))
    for b in range(first, last + 1):
        forwards, logits = denoising(B - (T % B if b == first else 0), B, S)
        total += forwards * block_pass(b) + 2.0 * w["head"] * logits
        if b < last:
            total += block_pass(b)
    return total
