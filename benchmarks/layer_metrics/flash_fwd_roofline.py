"""The flash-attention fwd kernel against its roofline: the least
time one call needs for its operations and bytes (roofline.py; causal,
no recomputation counted) over the time the trace gives a call.  Calls
that the backward pass repeats under rematerialisation are calls like
any other: the share is per call."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "train_tokens_per_s"


def read(ctx):
    from benchmarks import roofline
    from benchmarks.layer_metrics._common import kernel

    if ctx.get("plane") != "train" or "peaks" not in ctx:
        return None
    k = kernel(ctx, "flash_fwd")
    if k is None:
        return None
    m, mix = ctx["config"]["model"], ctx["traffic"]
    work = roofline.flash_fwd(mix["batch"], m["n_head"], mix["seq"],
                               m["n_embd"] // m["n_head"])
    return roofline.share(work, k["op_seconds"] / k["op_calls"], ctx["peaks"])
