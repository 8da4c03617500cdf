"""A block step's attention kernel against its roofline: the least time
one call (ONE layer, one forward) needs for the rows that were LIVE,
every cached K and V row of theirs read once for all `B x H` queries,
the block's `B` rows written, queries in and results out
(roofline_block_diffusion.py), over the time the trace gives a call.
The cached columns a forward's live rows attend are the device's own
count (`attended_tokens`: `pos + B` a live row), taken from the ticks of
the traced span, not the window's mean.  Memory-bound: 4 heads of 128
serve 4 x 32 queries a row."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_block_diffusion as rl
    from benchmarks.layer_metrics import _block_diffusion_common as c
    from benchmarks.layer_metrics._common import kernel, mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    k, ticks = kernel(ctx, "paged_decode"), c.traced_ticks(ctx)
    ticks = [t for t in ticks if t.get("attended_tokens")]
    if not k or not ticks:
        return None
    m, e, a = c.widths(ctx)
    work = rl.block_attention(
        mean(t["attended_tokens"] for t in ticks),
        mean(t["row_steps_live"] / e["chunk"] for t in ticks),
        a["block_length"], m["num_attention_heads"],
        m["num_key_value_heads"], m["head_dim"])
    return rl.share(work, k["op_seconds"] / k["op_calls"], ctx["peaks"])
