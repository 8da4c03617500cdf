"""Mean share of the engine's slots that held a live sequence, over
the ticks the engine's ring kept (the last `RT_ENGINE_TICK_RING`)."""
LAYER, UNIT, SOURCE, MOVES = "engine", "%", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve":
        return None
    slots = ctx["config"]["engine"]["slots"]
    ticks = [t["active"] for r in ctx["replicas"] for t in r["tick_ring"]]
    m = mean(ticks)
    return None if m is None else 100.0 * m / slots
