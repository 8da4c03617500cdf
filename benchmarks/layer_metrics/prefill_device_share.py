"""Share of the traced window the chip spends in the engine's
per-admission programs (prefill, suffix prefill, KV block write), which
run between decode chunks: found by the names the engine gives its
jitted families, mean over the replicas' chips.  None where the trace
has no `XLA Modules` line or the program names none of its families."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import mean, traces
    from benchmarks.layer_metrics._engine_phases import (DECODE, PREFILL,
                                                          program_seconds)

    if ctx.get("plane") != "serve":
        return None
    shares = [program_seconds(t, PREFILL) / t["window_s"]
              for t in traces(ctx)
              if t.get("window_s") and program_seconds(t, DECODE + PREFILL)]
    m = mean(shares)
    return None if m is None else 100.0 * m
