"""The routed experts of a FORWARD against their roofline: the device
time the trace puts under the `moe_routed` scope inside the decode
programs, per forward, against the least time the chip needs for the
forward's (token, expert) products (live rows x `B` positions x top-8:
32 rows an expert at 128 live rows) and for reading the experts that
were TOUCHED (`experts_touched`, the device's per-tick mean of distinct
(layer, expert) pairs a forward; roofline_block_diffusion.py), both
from the ticks of the traced span.  The scope holds the gather into
expert order, the three grouped products and the way back.
Memory-bound: 512 rows meet nearly every one of 128 experts, 4.7 MB
each."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_block_diffusion as rl
    from benchmarks.layer_metrics import _block_diffusion_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    sc = c.scopes(ctx)
    ticks = [t for t in c.traced_ticks(ctx) if t.get("experts_touched")]
    if not sc or not sc.get("moe_routed") or not ticks:
        return None
    m, e, a = c.widths(ctx)
    rows = mean(t["row_steps_live"] / e["chunk"] for t in ticks)
    work = rl.moe_routed(
        rows * a["block_length"] * m["num_experts_per_tok"],
        mean(t["experts_touched"] for t in ticks), m["num_hidden_layers"],
        m["hidden_size"], m["moe_intermediate_size"])
    return rl.share(work, sc["moe_routed"] / c.forwards(ctx, sc),
                    ctx["peaks"])
