"""The paged decode-attention kernel against its roofline: the least
time one call (one layer, one step) needs for the tokens that were
LIVE — every live K and V row read once (roofline.py) — over the time
the trace gives a call.  Live tokens are the engine's own per-tick
count, averaged over the run's ticks.  Memory-bound at these shapes."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline
    from benchmarks.layer_metrics._common import kernel, mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx:
        return None
    k = kernel(ctx, "paged_decode")
    live = mean(t["live_tokens"] for r in ctx["replicas"]
                for t in r["tick_ring"] if t["active"])
    if not k or not live:
        return None
    m, e = ctx["config"]["model"], ctx["config"]["engine"]
    work = roofline.paged_decode(live, e["slots"], m["num_attention_heads"],
                                 m["num_key_value_heads"], m["head_dim"])
    return roofline.share(work, k["op_seconds"] / k["op_calls"], ctx["peaks"])
