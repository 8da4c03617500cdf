"""Submit to admission (a slot and KV blocks held), p95 over the
window's finished requests: the engine's own lifecycle records."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", "request_p95_ms"


def read(ctx):
    from benchmarks import loadgen
    from benchmarks.layer_metrics._engine_phases import window_records

    if ctx.get("plane") != "serve":
        return None
    return loadgen.percentile(
        [1e3 * q["queue_s"] for q in window_records(ctx)], 95)
