"""The window layers' attention of a decode step against its roofline:
the device time the trace puts under the `swa_attn` scope inside the
decode programs, per step and window layer, against the least time the
chip needs to read each live row's `min(T, window)` window-latent rows
once (the tick ring's `window_rows_live`) and attend them in the
absorbed form at the window layers' widths (roofline_sparse_latent.py).
The scope holds the new row's write, the gather of the window's blocks
and the attention."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_sparse_latent as rl
    from benchmarks.layer_metrics import _sparse_latent_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    sc, ticks = c.scopes(ctx), c.ticks(ctx)
    if not sc or not sc.get("swa_attn") or not ticks:
        return None
    m, e, _ = c.widths(ctx)
    steps, layers = c.steps_and_layers(ctx, sc, "sliding_attention")
    work = rl.swa_decode(
        mean(t["window_rows_live"] for t in ticks),
        mean(c.live_rows(t, e["chunk"]) for t in ticks),
        m["swa_num_attention_heads"],
        m["swa_kv_lora_rank"] + m["swa_qk_rope_head_dim"],
        m["swa_kv_lora_rank"])
    return rl.share(work, sc["swa_attn"] / (steps * layers), ctx["peaks"])
