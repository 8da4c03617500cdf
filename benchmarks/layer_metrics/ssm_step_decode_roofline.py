"""The Mamba-2 layers of a decode step against their roofline: the
device time the trace puts under the `ssm_step`, `ssm_conv` and
`ssm_proj` scopes inside the decode programs, per step (the steps the
trace holds: `_recurrent_common.decode_steps`), against the
least time the chip needs to read and write the LIVE rows' float32
states (the engine's per-tick live rows a step) and convolution states
and to read each layer's two projections once (roofline_nemotron_h.py).
Memory-bound: the state's and the projections' bytes over the scopes'
device time."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_nemotron_h as rl
    from benchmarks.layer_metrics import _recurrent_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    sc, ticks = c.scopes(ctx), c.state_ticks(ctx)
    if not sc or not sc.get("ssm_step") or not ticks:
        return None
    m, e, _ = c.widths(ctx)
    steps = c.decode_steps(ctx, sc)
    seconds = sum(sc.get(k, 0.0) for k in ("ssm_step", "ssm_conv",
                                           "ssm_proj"))
    work = rl.ssm_step(
        mean(c.live_rows(t, e["chunk"]) for t in ticks), m["hidden_size"],
        m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
        m["ssm_state_size"], m["conv_kernel"])
    work = {k: v * c.mamba_layers(m) for k, v in work.items()}
    return rl.share(work, seconds / steps, ctx["peaks"])
