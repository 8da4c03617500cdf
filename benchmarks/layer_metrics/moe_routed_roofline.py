"""The routed experts of a decode step against their roofline: the
device time the trace puts under the `moe_routed` scope inside the
decode programs, per step, against the least time the chip needs for
the step's (token, expert) products and for reading the experts that
were TOUCHED (`experts_touched`, the engine's per-tick mean of distinct
(layer, expert) pairs a step; roofline_latent_moe.py).  The scope holds
the gather into expert order, the three grouped products and the way
back, so the share is of the whole routed part, not of one kernel.
Memory-bound at a decode batch: 64 rows meet ~0.95 of 128 experts."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_latent_moe as rl
    from benchmarks.layer_metrics._common import mean
    from benchmarks.layer_metrics._moe_common import expert_ticks, scopes

    if ctx.get("plane") != "serve" or "peaks" not in ctx:
        return None
    sc, ticks = scopes(ctx), expert_ticks(ctx)
    if not sc or not sc.get("moe_routed") or not ticks:
        return None
    m, e = ctx["config"]["model"], ctx["config"]["engine"]
    steps = sc["program_calls"] * e["chunk"]
    work = rl.moe_routed(
        e["slots"] * m["num_experts_per_tok"],
        mean(t["experts_touched"] for t in ticks),
        m["num_hidden_layers"] - m["first_k_dense_replace"],
        m["hidden_size"], m["moe_intermediate_size"])
    return rl.share(work, sc["moe_routed"] / steps, ctx["peaks"])
