"""Admission to the first token harvested on the host, p50 over the
window's finished requests: the prefill waits behind the chunk in
flight, and its token is read one chunk after the chunk that emits it."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", "request_p95_ms"


def read(ctx):
    from benchmarks import loadgen
    from benchmarks.layer_metrics._engine_phases import window_records

    if ctx.get("plane") != "serve":
        return None
    return loadgen.percentile(
        [1e3 * (q["first_token_s"] - q["queue_s"])
         for q in window_records(ctx)], 50)
