"""Imbalance of the HELD latent experts in decode: the most rows any one
held expert got in any step and layer of a chunk (`expert_load_max`)
over the mean rows a held expert gets (the tick's `held_pairs` over its
steps, expert layers and held experts), mean over the window's ticks.
1 is a flat load; the grouped product's time follows the largest
group."""
LAYER, UNIT, SOURCE, MOVES = "models", "ratio", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics import _recurrent_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or not c.widths(ctx):
        return None
    m, e, _ = c.widths(ctx)
    groups = (e["chunk"] * m["hybrid_override_pattern"].count("E")
              * m["n_routed_experts"])
    return mean(t["expert_load_max"] / (t["held_pairs"] / groups)
                for t in c.expert_ticks(ctx) if t["held_pairs"])
