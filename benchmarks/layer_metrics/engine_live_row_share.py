"""The share of the decode chunks' row-steps (slots x chunk a tick) that
a request was waiting for: `row_steps_live / row_steps` over the
window.  The rest were dead rows: empty slots, a budget that ended
inside a chunk, a finished row not harvested yet."""
LAYER, UNIT, SOURCE, MOVES = "engine", "%", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._engine_account import window_sums

    a = window_sums(ctx)
    if a is None or not a["row_steps"]:
        return None
    return 100.0 * a["row_steps_live"] / a["row_steps"]
