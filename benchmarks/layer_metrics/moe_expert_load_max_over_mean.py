"""How uneven the router's load is in a decode step: the most rows any
one expert got in a tick (`expert_load_max`, over its steps and layers)
over the mean rows an expert gets (slots x top-k / experts), averaged
over the ticks.  1 = perfectly even; the grouped product's longest
group, and with experts across chips the slowest chip, follow it."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ratio", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import mean
    from benchmarks.layer_metrics._moe_common import expert_ticks

    ticks = expert_ticks(ctx) if ctx.get("plane") == "serve" else []
    if not ticks:
        return None
    m, e = ctx["config"]["model"], ctx["config"]["engine"]
    even = e["slots"] * m["num_experts_per_tok"] / m["n_routed_experts"]
    return mean(t["expert_load_max"] for t in ticks) / even
