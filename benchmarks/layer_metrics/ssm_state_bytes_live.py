"""What the live batch holds in per-slot states: a tick's live rows x
the bytes a slot's float32 recurrent states and convolution states cost
over the Mamba layers (`ssm_bytes_live`, from the engine's own
`cache_bytes_per_slot`), mean over the window's ticks that dispatched a
chunk.  It does not grow with the contexts: the paged K and V of the
one attention layer are `engine_*`'s to report."""
LAYER, UNIT, SOURCE, MOVES = "engine", "bytes", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics import _recurrent_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or not c.widths(ctx):
        return None
    return mean(t["ssm_bytes_live"] for t in c.state_ticks(ctx))
