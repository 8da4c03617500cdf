"""Model FLOP/s utilisation: tokens/s x FLOPs a token needs (forward
and backward, causal attention, no recomputation: roofline.py) over
chips x the published bf16 peak."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "host_clock", "train_tokens_per_s"


def read(ctx):
    from benchmarks import roofline

    if ctx.get("plane") != "train" or "peaks" not in ctx:
        return None
    m, t = ctx["config"]["model"], ctx["train"]
    E, L, V = m["n_embd"], m["n_layer"], m["vocab_size"]
    per_token = roofline.dense_train_flops_per_token(
        12 * L * E * E + V * E, L, ctx["traffic"]["seq"], E)
    rate = t["steps"] * t["tokens_per_step"] / t["elapsed_s"]
    chips = int(ctx["cell"]["chips"])
    return 100.0 * rate * per_token / (chips * ctx["peaks"]["bf16_flops_per_s"])
