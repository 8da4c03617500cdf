"""This chip's SHARE (128 of 512) of the latent experts of a decode step
against its roofline: the device time the trace puts under the
`latent_moe_routed` scope inside the decode programs, per step (the steps
the trace holds: `_recurrent_common.decode_steps`), against
the least time the chip needs to read the HELD experts that were TOUCHED
(`experts_touched`, the engine's per-tick mean of distinct (layer, held
expert) pairs a step; two matrices of 1024 x 2688, 11.0 MB an expert)
and to run the live rows' pairs that fall to them
(roofline_nemotron_h.py).  The expert layers are the `E`s of
`hybrid_override_pattern`."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_nemotron_h as rl
    from benchmarks.layer_metrics import _recurrent_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    sc, ticks = c.scopes(ctx), c.expert_ticks(ctx)
    if not sc or not sc.get("latent_moe_routed") or not ticks:
        return None
    m, e, dep = c.widths(ctx)
    steps = c.decode_steps(ctx, sc)
    live = [t for t in ticks if t.get("row_steps_live")]
    work = rl.latent_moe_routed(
        mean(c.live_rows(t, e["chunk"]) for t in live) or e["slots"],
        m["num_experts_per_tok"], m["n_routed_experts"],
        dep["router_experts"], mean(t["experts_touched"] for t in ticks),
        m["hybrid_override_pattern"].count("E"), m["moe_latent_size"],
        m["moe_intermediate_size"])
    return rl.share(work, sc["latent_moe_routed"] / steps, ctx["peaks"])
