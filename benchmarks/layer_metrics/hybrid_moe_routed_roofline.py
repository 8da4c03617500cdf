"""The hybrid model's routed experts of a decode step against their
roofline: the device time the trace puts under the `moe_routed` scope
inside the decode programs, per step, against the least time the chip
needs for the live rows' (token, expert) products and for reading the
experts that were TOUCHED (`experts_touched`, the engine's per-tick mean
of distinct (layer, expert) pairs a step, x 22.0 MB;
roofline_hybrid.py).  The scope holds the gather into expert order, the
three grouped products and the way back, so the share is of the whole
routed part, not of one kernel.  Memory-bound at a decode batch: ~115
live rows x 4 meet all 32 experts of every layer, 14-16 rows each."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_hybrid as rl
    from benchmarks.layer_metrics._common import mean
    from benchmarks.layer_metrics._hybrid_common import hybrid_ticks, live_rows
    from benchmarks.layer_metrics._moe_common import scopes

    if ctx.get("plane") != "serve" or "peaks" not in ctx:
        return None
    sc, ticks = scopes(ctx), hybrid_ticks(ctx)
    if not sc or not sc.get("moe_routed") or not ticks:
        return None
    m, e = ctx["config"]["model"], ctx["config"]["engine"]
    steps = sc["program_calls"] * e["chunk"]
    work = rl.moe_routed(
        live_rows(ctx, ticks) * m["num_experts_per_tok"],
        mean(t["experts_touched"] for t in ticks),
        m["num_hidden_layers"] - m["num_dense_layers"],
        m["hidden_size"], m["moe_intermediate_size"])
    return rl.share(work, sc["moe_routed"] / steps, ctx["peaks"])
