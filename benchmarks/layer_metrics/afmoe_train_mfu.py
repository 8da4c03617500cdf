"""The whole step's share of the chip's peak: tokens/s x the FLOPs a
token needs (forward and backward; window layers at `min(i + 1,
window)` keys; one held expert a token by expectation; no
recomputation: `needed_flops/train_window_moe.py`, counted from the
configuration and the mix alone) over chips x the published bf16
peak."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "host_clock", "train_tokens_per_s"


def read(ctx):
    from benchmarks import manifest
    from benchmarks.layer_metrics._train_window_moe_common import mine

    t = mine(ctx)
    if t is None or "peaks" not in ctx or not t.get("elapsed_s"):
        return None
    per_token = manifest.needed_flops("train_window_moe").token_flops(
        ctx["config"], ctx["traffic"])
    rate = t["steps"] * t["tokens_per_step"] / t["elapsed_s"]
    return 100.0 * rate * per_token / (
        int(ctx["cell"]["chips"]) * ctx["peaks"]["bf16_flops_per_s"])
