"""Share of the step programs' device time spent in the optimizer's update (the clip's norm, AdamW, the parameters' add):
the trace's time under the scopes optimizer, forward and
backward, over the time of the `jit_step` programs."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "train_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._train_window_moe_common import scope_share

    return scope_share(ctx, ('optimizer',))
