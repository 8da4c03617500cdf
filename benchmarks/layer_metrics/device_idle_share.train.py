"""1 - the union of the device's op intervals over the traced window."""
LAYER, UNIT, SOURCE, MOVES = "device", "%", "device_trace", "train_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import mean, traces

    if ctx.get("plane") != "train":
        return None
    m = mean(t["idle_share"] for t in traces(ctx))
    return None if m is None else 100.0 * m
