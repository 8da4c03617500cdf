"""Ticks of the window that STALLED: over 1 s and over four times the
engine's tick EMA before them, with nothing to compile (the engine's
own rule, `llm_engine.STALL_MIN_S` / `STALL_FACTOR`; it keeps each such
tick whole, with its neighbours, in `stats()["stalls"]`)."""
LAYER, UNIT, SOURCE, MOVES = "engine", "count", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._engine_account import window_sums

    a = window_sums(ctx)
    if a is None:
        return None
    return float(a["stalled"])
