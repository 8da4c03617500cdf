"""The share of the live rows' forwards that were COMMITS (a block's
last forward, whose input has no mask: it decides nothing, it writes the
block's K and V rows as they stay), over the window, from the device's
own counts.  One of `S + 1` forwards a block: 31% over the cell's mix
of steps.  A commit needs neither the head nor the confidence
(`blockgen_head_confidence_ms`), and fused into the next block's first
forward it would cost no forward at all."""
LAYER, UNIT, SOURCE, MOVES = ("engine", "%", "program_counter",
                              "serve_tokens_per_s")


def read(ctx):
    from benchmarks.layer_metrics import _block_diffusion_common as c

    if ctx.get("plane") != "serve" or not c.widths(ctx):
        return None
    ticks = c.ticks(ctx)
    if not ticks:
        return None
    return (100.0 * sum(t["commit_row_steps"] for t in ticks)
            / sum(t["row_steps_live"] for t in ticks))
