"""Routing imbalance as the step counts it: the largest count any of
the router's experts got over the mean count, the expert layers
averaged, the window's steps averaged (`expert_load_max` /
`expert_load_mean`, which every step reports)."""
LAYER, UNIT, SOURCE, MOVES = "models", "x", "program_counter", "train_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._train_window_moe_common import mine

    t = mine(ctx)
    sm = (t or {}).get("step_metrics") or {}
    pairs = [(a, b) for a, b in zip(sm.get("expert_load_max", []),
                                    sm.get("expert_load_mean", [])) if b]
    if not pairs:
        return None
    return sum(a / b for a, b in pairs) / len(pairs)
