"""Share of the decode programs' device time spent in the expert
layers: the trace's time under the scopes `moe_router`, `moe_routed`
and `moe_shared` over the time of the `jit_decode_chunk_*` programs
that hold them."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._moe_common import scopes

    if ctx.get("plane") != "serve":
        return None
    sc = scopes(ctx)
    parts = [sc.get(k, 0.0) for k in ("moe_router", "moe_routed",
                                      "moe_shared")] if sc else []
    if not sc or not sc.get("programs_s") or not sum(parts):
        return None
    return 100.0 * sum(parts) / sc["programs_s"]
