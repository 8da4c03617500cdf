"""Admission chunks that RESUMED from a slot's state, a request admitted
in the window: the tick ring's `state_chunks_resumed` (a chunk of a long
prompt whose Mamba layers started from what the chunk before it left in
the slot) summed over the window's ticks, over the requests those ticks
admitted.  The mix's 1,024 / 4,096 / 8,192 at 2 : 1 : 1 in chunks of
2,048 gives (0 + 0 + 1 + 3) / 4 = 1."""
LAYER, UNIT, SOURCE, MOVES = "engine", "chunks/request", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics import _recurrent_common as c

    if ctx.get("plane") != "serve" or not c.widths(ctx):
        return None
    ticks = c.window_ticks(ctx)
    admitted = sum(t.get("admitted", 0) for t in ticks)
    if not admitted or not any("state_chunks_resumed" in t for t in ticks):
        return None
    return sum(t.get("state_chunks_resumed", 0) for t in ticks) / admitted
