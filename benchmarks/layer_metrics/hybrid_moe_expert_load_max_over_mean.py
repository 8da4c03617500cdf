"""How uneven the hybrid model's router loads its experts in a decode
step: the most rows any one expert got in a tick (`expert_load_max`,
over its steps and expert layers) over the mean rows an expert gets
from the rows that were LIVE in that tick (live rows x top-k /
`num_experts`), averaged over the ticks.  1 = perfectly even; the
grouped product's longest group follows it.  The twin of
`moe_expert_load_max_over_mean`, which takes the expert count from
`n_routed_experts` and every slot as live: this model's config spells
it `num_experts`, and a fifth of its slots wait for a harvest."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ratio", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import mean
    from benchmarks.layer_metrics._hybrid_common import hybrid_ticks

    if ctx.get("plane") != "serve":
        return None
    m, e = ctx["config"]["model"], ctx["config"]["engine"]
    if "num_experts" not in m:
        return None
    pairs = m["num_experts_per_tok"] / (m["num_experts"] * e["chunk"])
    return mean(t["expert_load_max"] / (t["row_steps_live"] * pairs)
                for t in hybrid_ticks(ctx)
                if t.get("expert_load_max") and t.get("row_steps_live"))
