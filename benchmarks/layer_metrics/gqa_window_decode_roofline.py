"""The window layers' attention of a decode step against its roofline:
the device time the trace puts under the `swa_attn` scope inside the
decode programs, per step and window layer, against the least time the
chip needs to read each live row's `min(T, window)` ring rows once (the
tick ring's `window_rows_live`; 8 heads of 192 + 128, 5,120 B a row) and
attend them (roofline_window_full.py).  Plain XLA on the slot's ring:
the scope holds the read of the ring and the attention; the row's write
is under `swa_ring_write`."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_window_full as rl
    from benchmarks.layer_metrics import _window_full_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    sc, ticks = c.scopes(ctx), c.ticks(ctx)
    if not sc or not sc.get("swa_attn") or not ticks:
        return None
    m, e, _ = c.widths(ctx)
    steps = sc["program_calls"] * e["chunk"]
    layers = sum(m["hybrid_layer_pattern"])
    work = rl.gqa_decode(
        mean(t["window_rows_live"] for t in ticks),
        mean(c.live_rows(t, e["chunk"]) for t in ticks),
        m["swa_num_attention_heads"], m["swa_num_key_value_heads"],
        m["swa_head_dim"], m["swa_v_head_dim"])
    return rl.share(work, sc["swa_attn"] / (steps * layers), ctx["peaks"])
