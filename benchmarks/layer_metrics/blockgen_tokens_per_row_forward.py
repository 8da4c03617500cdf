"""Tokens a live row's FORWARD commits, over the window: the device's
own counts (`tokens_committed / row_steps_live`, both from the chunk's
one read).  A block of `B` = 4 positions takes `S` denoising forwards
and one commit, so a request of `S` steps reads `B / (S + 1)`: 2, 1.33
and 0.8 at 1, 2 and 4 steps, 1.23 over the cell's mix.  This is what
converts the shared readers' per-STEP readings of this cell
(`decode_step_ms`, `engine_inter_token_p50_ms`: one FORWARD here, not
one token) into tokens."""
LAYER, UNIT, SOURCE, MOVES = ("engine", "tokens/forward", "program_counter",
                              "serve_tokens_per_s")


def read(ctx):
    from benchmarks.layer_metrics import _block_diffusion_common as c

    if ctx.get("plane") != "serve" or not c.widths(ctx):
        return None
    ticks = c.ticks(ctx)
    if not ticks:
        return None
    return (sum(t["tokens_committed"] for t in ticks)
            / sum(t["row_steps_live"] for t in ticks))
