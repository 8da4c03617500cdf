"""Share of the hybrid model's decode programs' device time spent in
the expert layers: the trace's time under the scopes `moe_router` and
`moe_routed` (it has no shared expert) over the time of the
`jit_decode_chunk_*` programs that hold them, where `short_conv` is
also found.  Most of a step: the step is the experts' weight read."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._moe_common import scopes

    if ctx.get("plane") != "serve":
        return None
    sc = scopes(ctx)
    if not sc or not sc.get("programs_s") or not sc.get("short_conv"):
        return None
    parts = sc.get("moe_router", 0.0) + sc.get("moe_routed", 0.0)
    return 100.0 * parts / sc["programs_s"] if parts else None
