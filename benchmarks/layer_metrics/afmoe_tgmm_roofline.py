"""The held experts' grouped products, matrices' gradient (three products a held pair, megablox's tgmm), against their roofline:
the work of the traced steps' `held_pairs` (the step's own counter;
`roofline_train_window_moe.py`, no recomputation counted) over the
time the trace gives the kernel's calls, a remat block's replay among
them."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "train_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_train_window_moe as r
    from benchmarks.layer_metrics._train_window_moe_common import (
        expert_work, kernel_share)

    work = expert_work(ctx, r.tgmm)
    return None if work is None else kernel_share(ctx, "afmoe_tgmm", work)
