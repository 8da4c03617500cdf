"""The latent decode-attention kernel against its roofline: the least
time one call (one layer, one step) needs for the tokens that were LIVE
— every live token's 576-value row read once, serving all 32 heads as
key and as value (roofline_latent_moe.py) — over the time the trace
gives a call.  Live tokens are the engine's per-tick count, averaged
over the run's ticks.  Memory-bound: ~60 flop/B against the chip's 240."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_latent_moe as rl
    from benchmarks.layer_metrics._common import kernel, mean

    m = ctx.get("config", {}).get("model", {})
    if (ctx.get("plane") != "serve" or "peaks" not in ctx
            or "kv_lora_rank" not in m):
        return None
    k = kernel(ctx, "paged_decode")
    live = mean(t["live_tokens"] for r in ctx["replicas"]
                for t in r["tick_ring"] if t["active"])
    if not k or not live:
        return None
    work = rl.mla_decode(live, ctx["config"]["engine"]["slots"],
                         m["num_attention_heads"],
                         m["kv_lora_rank"] + m["qk_rope_head_dim"],
                         m["kv_lora_rank"])
    return rl.share(work, k["op_seconds"] / k["op_calls"], ctx["peaks"])
