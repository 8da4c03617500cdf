"""Rows that owed a token at a decode chunk's first step
(`state_rows_live` in the tick ring of an engine whose cache is one
state a slot): the states that step reads and writes, mean over the
ring's ticks that dispatched a chunk.  The decode kernel's time follows
it, not the slots."""
LAYER, UNIT, SOURCE, MOVES = "engine", "count", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import mean
    from benchmarks.layer_metrics._retention_common import state_ticks

    if ctx.get("plane") != "serve":
        return None
    return mean(t["state_rows_live"] for t in state_ticks(ctx))
