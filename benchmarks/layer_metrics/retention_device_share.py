"""Share of the decode programs' device time spent in the retention
half of the layers: the trace's time under the scope `retention_attn`
(projections, norms, rotary, gate, the state step, `W_o`) over the time
of the `jit_decode_chunk_*` programs that hold it.  Over half: the
mechanism does most of a step's work."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._retention_common import scopes

    if ctx.get("plane") != "serve":
        return None
    sc = scopes(ctx)
    if not sc or not sc.get("programs_s") or not sc.get("retention_attn"):
        return None
    return 100.0 * sc["retention_attn"] / sc["programs_s"]
