"""How device-bound the engine's loop is: the time its ticks spent in
the BLOCKING read of the previous chunk's tokens (`device_wait_s`), as a
share of the ticks' wall and the loop's blocked time between them, over
the whole window."""
LAYER, UNIT, SOURCE, MOVES = "engine", "%", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._engine_account import share, window_sums

    a = window_sums(ctx)
    if a is None:
        return None
    return share(a["device_wait_us"], a)
