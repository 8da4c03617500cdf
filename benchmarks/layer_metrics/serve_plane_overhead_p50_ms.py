"""Client-side duration of a request minus the duration the
benchmark's deployment measured around `engine.submit`: what proxy,
router, replica and the HTTP hop add.  Two durations, no shared clock."""
LAYER, UNIT, SOURCE, MOVES = "serve plane", "ms", "host_clock", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import med

    if ctx.get("plane") != "serve":
        return None
    return med(ctx["client"]["plane_overhead_ms"])
