"""What one generated token costs a request: first harvest to last,
over the tokens the chunks between them made, p50 over the window's
finished requests fed by two chunks or more.  Set it beside
`decode_step_ms`, which is the device's time for one step alone."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import loadgen
    from benchmarks.layer_metrics._engine_phases import window_records

    if ctx.get("plane") != "serve":
        return None
    chunk = ctx["config"]["engine"]["chunk"]
    return loadgen.percentile(
        [1e3 * q["decode_s"] / ((q["harvests"] - 1) * chunk)
         for q in window_records(ctx) if q["harvests"] >= 2], 50)
