"""Host batch drawn and placed on the device, per step (mean)."""
LAYER, UNIT, SOURCE, MOVES = "train plane", "ms", "host_clock", "train_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "train":
        return None
    m = mean(ctx["train"]["spans"]["data"])
    return None if m is None else m * 1e3
