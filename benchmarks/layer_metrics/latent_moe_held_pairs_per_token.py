"""(row, expert) pairs a live row-step sends to experts THIS CHIP HOLDS,
an expert layer: the tick ring's `held_pairs` (the decode program's own
count, summed over a chunk's steps and the expert layers) over the
tick's live row-steps and the expert layers, over the window.  The
router takes the top 22 of 512 and 128 are held: 5.5 expected under a
uniform router; a router that favours this chip's experts reads higher
and costs this chip more."""
LAYER, UNIT, SOURCE, MOVES = "models", "pairs/token", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics import _recurrent_common as c

    if ctx.get("plane") != "serve" or not c.widths(ctx):
        return None
    ticks = [t for t in c.expert_ticks(ctx) if t.get("row_steps_live")]
    steps = sum(t["row_steps_live"] for t in ticks)
    if not steps:
        return None
    m, _, _ = c.widths(ctx)
    return sum(t["held_pairs"] for t in ticks) / steps / \
        m["hybrid_override_pattern"].count("E")
