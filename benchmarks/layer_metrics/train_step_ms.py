"""The jitted step through its device->host read of the loss (median):
the steady statistic beside `train_tokens_per_s`."""
LAYER, UNIT, SOURCE, MOVES = "models", "ms", "host_clock", "train_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import med

    if ctx.get("plane") != "train":
        return None
    m = med(ctx["train"]["spans"]["step"])
    return None if m is None else m * 1e3
