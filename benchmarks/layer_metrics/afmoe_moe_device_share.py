"""Share of the step programs' device time spent in the expert layers (router, routed and shared experts):
the trace's time under the scopes moe_router, moe_routed, moe_shared, forward and
backward, over the time of the `jit_step` programs."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "train_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._train_window_moe_common import scope_share

    return scope_share(ctx, ('moe_router', 'moe_routed', 'moe_shared'))
