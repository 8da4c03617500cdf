"""Device time of one decode step: the traced executions of the
engine's chunk program (the programs that hold the paged-attention
kernel), over the steps they ran."""
LAYER, UNIT, SOURCE, MOVES = "models", "ms", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import kernel

    if ctx.get("plane") != "serve":
        return None
    k = kernel(ctx, "paged_decode")
    if k is None:
        return None
    return 1e3 * k["seconds"] / (ctx["config"]["engine"]["chunk"] * k["calls"])
