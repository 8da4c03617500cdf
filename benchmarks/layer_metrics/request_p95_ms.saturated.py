"""Whole-request p95 at the client in a cell that runs past capacity:
recorded, never a judge there."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "host_clock", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.loadgen import percentile

    if ctx.get("plane") != "serve":
        return None
    return percentile(ctx["client"]["latency_ms"], 95)
