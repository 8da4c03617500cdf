"""The paged decode-attention kernel at head width 64 against its
roofline: the least time one call (ONE of the model's attention layers,
one step) needs for the tokens that were LIVE, every live K and V row
(8 heads of 64, 2 KB a token) read once (roofline_hybrid.py), over the
time the trace gives a call.  Live tokens are the engine's own per-tick
count, averaged over the run's ticks.  Memory-bound.  The pool holds
a token's heads side by side in one row of whole lanes, so the kernel
reads these bytes and no padding."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_hybrid as rl
    from benchmarks.layer_metrics._common import kernel, mean
    from benchmarks.layer_metrics._hybrid_common import hybrid_ticks

    if ctx.get("plane") != "serve" or "peaks" not in ctx:
        return None
    k, ticks = kernel(ctx, "paged_decode"), hybrid_ticks(ctx)
    if not k or not ticks:
        return None
    cfg = ctx["config"]
    m, e = cfg["model"], cfg["engine"]
    work = rl.paged_decode(
        mean(t["live_tokens"] for t in ticks), e["slots"],
        m["num_attention_heads"], m["num_key_value_heads"],
        cfg["assumed"]["head_dim"])
    return rl.share(work, k["op_seconds"] / k["op_calls"], ctx["peaks"])
