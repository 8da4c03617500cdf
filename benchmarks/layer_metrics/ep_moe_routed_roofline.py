"""This chip's SHARE of the routed experts of a decode step against its
roofline: the device time the trace puts under the `moe_routed` scope
inside the decode programs, per step, against the least time the chip
needs to read the HELD experts that were touched (`experts_touched`,
the engine's per-tick mean of distinct (layer, held expert) pairs a
step) and to run the live rows' pairs that fall to them
(roofline_sparse_latent.py).  The twin of `moe_routed_roofline`, which
counts every slot as live and every routed expert as held."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_sparse_latent as rl
    from benchmarks.layer_metrics import _sparse_latent_common as c
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
        m["num_hidden_layers"] - m["first_k_dense_replace"],
        m["hidden_size"], m["moe_intermediate_size"])
    return rl.share(work, sc["moe_routed"] / steps, ctx["peaks"])
