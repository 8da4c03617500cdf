"""This chip's SHARE (16 of 256) of the routed experts of a decode step
against its roofline: the device time the trace puts under the
`moe_routed` scope inside the decode programs, per step, against the
least time the chip needs to read the HELD experts that were touched
(`experts_touched`, the engine's per-tick mean of distinct (layer, held
expert) pairs a step; 50.3 MB an expert) and to run the live rows' pairs
that fall to them (roofline_window_full.py).  The twin of
`ep_moe_routed_roofline` at this configuration's keys: the expert layers
are those `moe_layer_freq` names."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_window_full as rl
    from benchmarks.layer_metrics import _window_full_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    sc, ticks = c.scopes(ctx), c.expert_ticks(ctx)
    if not sc or not sc.get("moe_routed") or not ticks:
        return None
    m, e, dep = c.widths(ctx)
    steps = sc["program_calls"] * e["chunk"]
    live = [t for t in ticks if t.get("row_steps_live")]
    work = rl.ep_moe_routed(
        mean(c.live_rows(t, e["chunk"]) for t in live) or e["slots"],
        m["num_experts_per_tok"], m["n_routed_experts"],
        dep["router_experts"], mean(t["experts_touched"] for t in ticks),
        sum(m["moe_layer_freq"]), m["hidden_size"],
        m["moe_intermediate_size"])
    return rl.share(work, sc["moe_routed"] / steps, ctx["peaks"])
