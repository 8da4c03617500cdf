"""The grouped, windowed flash-attention bwd kernels against their
roofline: the least time a step's calls need for their operations and
bytes, summed over the layers by their kind (window layers at `min(i +
1, window)` keys: `roofline_train_window_moe.py`; no recomputation
counted), over the time the trace gives ALL the calls of a step."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "train_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_train_window_moe as r
    from benchmarks.layer_metrics._train_window_moe_common import (
        kernel_share, mine)

    if mine(ctx) is None:
        return None
    m, mix = ctx["config"]["model"], ctx["traffic"]

    def work(calls):
        w = r.step_calls(m, int(mix["batch"]), int(mix["seq"]), r.flash_bwd)
        return {k: v * calls for k, v in w.items()}

    return kernel_share(ctx, "afmoe_flash_bwd", work)
