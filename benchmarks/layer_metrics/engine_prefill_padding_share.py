"""What the prefill programs' sizes waste: `1 - prefill_tokens /
prefill_padded_tokens` over the window (the real tokens the window's
prefill programs carried against the sizes they were compiled for)."""
LAYER, UNIT, SOURCE, MOVES = "engine", "%", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._engine_account import window_sums

    a = window_sums(ctx)
    if a is None or not a["prefill_padded_tokens"]:
        return None
    return 100.0 * (1.0 - a["prefill_tokens"] / a["prefill_padded_tokens"])
