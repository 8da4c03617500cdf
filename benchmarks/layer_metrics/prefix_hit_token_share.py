"""Of the prompt tokens the engine admitted inside the WINDOW, the share
it did not prefill because a cached prefix held them: the tick records'
`prefix_hit_tokens` over `prefix_hit_tokens + prefill_tokens`, summed
over the ring's ticks that began in the window (a traced run keeps
every tick).  None for a program whose ticks carry no such field."""
LAYER, UNIT, SOURCE, MOVES = "engine", "%", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._sparse_latent_common import window_ticks

    if ctx.get("plane") != "serve":
        return None
    ticks = window_ticks(ctx)
    if not any("prefix_hit_tokens" in t for t in ticks):
        return None
    hit = sum(t.get("prefix_hit_tokens", 0) for t in ticks)
    total = hit + sum(t.get("prefill_tokens", 0) for t in ticks)
    return 100.0 * hit / total if total else None
