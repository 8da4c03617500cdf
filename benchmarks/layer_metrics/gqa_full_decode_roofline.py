"""The paged decode-attention kernel at keys of 192 and values of 128
against its roofline: the least time one call (ONE of the model's full
layers, one step) needs for the tokens that were LIVE, every live K row
(4 heads of 192) and V row (4 of 128) read once, 2,560 B a token
(roofline_window_full.py), over the time the trace gives a call.  Live
tokens are the engine's own per-tick count of what a full layer reads
for the live rows (`full_cache_tokens_live`), averaged over the window's
ticks.  Memory-bound.  The pools hold a token's heads side by side in
one row of whole lanes, so the kernel reads these bytes and no padding."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_window_full as rl
    from benchmarks.layer_metrics import _window_full_common as c
    from benchmarks.layer_metrics._common import kernel, mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    k, ticks = kernel(ctx, "paged_decode"), c.ticks(ctx)
    if not k or not ticks:
        return None
    m, e, _ = c.widths(ctx)
    work = rl.gqa_decode(
        mean(t["full_cache_tokens_live"] for t in ticks),
        mean(c.live_rows(t, e["chunk"]) for t in ticks),
        m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
        m["v_head_dim"])
    return rl.share(work, k["op_seconds"] / k["op_calls"], ctx["peaks"])
