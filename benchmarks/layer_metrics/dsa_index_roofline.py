"""The indexer of a decode step against its roofline: the device time
the trace puts under the `dsa_index` scope inside the decode programs,
per step and full layer, against the least time the chip needs to read
every live token's index key once and score it (`live_tokens` of the
tick ring; roofline_sparse_latent.py).  The scope holds the index
projections, the new key's write, the gather of a row's keys through
its table and the scores, so the share is of the whole pass."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_sparse_latent as rl
    from benchmarks.layer_metrics import _sparse_latent_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    sc, ticks = c.scopes(ctx), c.ticks(ctx)
    if not sc or not sc.get("dsa_index") or not ticks:
        return None
    m, e, _ = c.widths(ctx)
    steps, layers = c.steps_and_layers(ctx, sc, "full_attention")
    work = rl.dsa_index(mean(t["live_tokens"] for t in ticks),
                        mean(c.live_rows(t, e["chunk"]) for t in ticks),
                        m["index_n_heads"], m["index_head_dim"])
    return rl.share(work, sc["dsa_index"] / (steps * layers), ctx["peaks"])
