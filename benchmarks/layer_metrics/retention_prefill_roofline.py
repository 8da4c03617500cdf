"""The chunked-scan prefill kernel against its roofline: the least time
one call (one layer of one packed prefill) needs, the larger of its
operations over the chip's peak and its bytes over the bandwidth
(roofline_retention.py), over the time the trace gives a call.  The
tokens and prompts a call holds are the engine's own totals over its
programs (`prefill_tokens`, `prefill_rows`, `prefill_calls`: means are
exact here, the count is linear in both).  Compute-bound: the carried
state's read-out is 2 * 8,256 * 129 operations a token and query
head."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_retention as rl
    from benchmarks.layer_metrics._common import kernel

    if ctx.get("plane") != "serve" or "peaks" not in ctx:
        return None
    k = kernel(ctx, "retention_prefill")
    eng = [r["engine"] for r in ctx["replicas"]
           if r.get("engine", {}).get("prefill_calls")]
    if not k or not eng:
        return None
    calls = sum(e["prefill_calls"] for e in eng)
    m, e = ctx["config"]["model"], ctx["config"]["engine"]
    work = rl.retention_prefill(
        sum(g["prefill_tokens"] for g in eng) / calls,
        sum(g["prefill_rows"] for g in eng) / calls, e["block_size"],
        m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"])
    return rl.share(work, k["op_seconds"] / k["op_calls"], ctx["peaks"])
