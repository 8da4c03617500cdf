"""Host time of one engine tick (admit + dispatch + harvest), mean over
the ring: what the engine thread spends per chunk of decode steps."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve":
        return None
    m = mean(t["admit_s"] + t["dispatch_s"] + t["harvest_s"]
             for r in ctx["replicas"] for t in r["tick_ring"])
    return None if m is None else m * 1e3
