"""The selected attention of a decode step against its roofline: the
device time the trace puts under the `dsa_attn` scope inside the decode
programs, per step and full layer, against the least time the chip
needs to read each live row's SELECTED latent rows once (`min(T,
index_topk)` of them: the tick ring's `dsa_selected_share` x
`live_tokens`) and attend them in the absorbed form
(roofline_sparse_latent.py).  The scope holds the gather by flat pool
index and the attention."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_sparse_latent as rl
    from benchmarks.layer_metrics import _sparse_latent_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    sc, ticks = c.scopes(ctx), c.ticks(ctx)
    if not sc or not sc.get("dsa_attn") or not ticks:
        return None
    m, e, _ = c.widths(ctx)
    steps, layers = c.steps_and_layers(ctx, sc, "full_attention")
    rows = mean(c.live_rows(t, e["chunk"]) for t in ticks)
    # every row of the cell is past index_topk, so a row selects that many
    selected = rows * min(m["index_topk"], mean(
        t["live_tokens"] / max(1, t["active"]) for t in ticks))
    work = rl.dsa_sparse_decode(
        selected, rows, m["num_attention_heads"],
        m["kv_lora_rank"] + m["qk_rope_head_dim"], m["kv_lora_rank"])
    return rl.share(work, sc["dsa_attn"] / (steps * layers), ctx["peaks"])
