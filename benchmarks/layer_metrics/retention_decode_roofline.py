"""The retention decode kernel against its roofline: the least time one
call (one layer, one step) needs for the rows that were LIVE, each
row's state and key sum read and written once at the 8,256 distinct
monomials a KV head (34.08 MB a row, roofline_retention.py), over the
time the trace gives a call.  Live rows a step are the engine's own
count: a tick's live row-steps over its chunk's steps, averaged over
the ring.  Memory-bound: ~3 flop/B."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_retention as rl
    from benchmarks.layer_metrics._common import kernel, mean
    from benchmarks.layer_metrics._retention_common import state_ticks

    if ctx.get("plane") != "serve" or "peaks" not in ctx:
        return None
    k, ticks = kernel(ctx, "retention_decode"), state_ticks(ctx)
    if not k or not ticks:
        return None
    m, e = ctx["config"]["model"], ctx["config"]["engine"]
    live = mean(t["row_steps_live"] / e["chunk"] for t in ticks)
    work = rl.retention_decode(live, m["num_attention_heads"],
                               m["num_key_value_heads"], m["head_dim"])
    return rl.share(work, k["op_seconds"] / k["op_calls"], ctx["peaks"])
