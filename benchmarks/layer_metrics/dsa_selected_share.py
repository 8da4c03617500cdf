"""The share of its context a full layer attends: the mean over the
live rows of `min(T, index_topk) / T` (the tick ring's
`dsa_selected_share`), mean over the ring's ticks that dispatched a
chunk.  1 while every context is under `index_topk` (the selection
idle), 0.12 at 16.6k tokens."""
LAYER, UNIT, SOURCE, MOVES = "models", "ratio", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import mean
    from benchmarks.layer_metrics._sparse_latent_common import ticks

    if ctx.get("plane") != "serve":
        return None
    return mean(t["dsa_selected_share"] for t in ticks(ctx))
