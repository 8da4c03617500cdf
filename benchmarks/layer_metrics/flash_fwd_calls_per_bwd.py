"""How often the flash-attention fwd kernel runs for one run of the
bwd kernel, by the calls the trace counts: 2.0 where the backward
pass's replay under rematerialisation runs the forward kernel again,
1.0 where the block keeps the kernel's results
(`ray_tpu.ops.attention.FLASH_RESIDUALS`) and the replay reads them."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "ratio", "device_trace", "train_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import kernel

    if ctx.get("plane") != "train":
        return None
    fwd, bwd = kernel(ctx, "flash_fwd"), kernel(ctx, "flash_bwd")
    if fwd is None or bwd is None:
        return None
    return fwd["op_calls"] / bwd["op_calls"]
