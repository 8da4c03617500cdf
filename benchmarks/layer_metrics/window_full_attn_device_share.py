"""Share of the decode programs' device time spent in attention of
either kind: the trace's time under the scopes `full_attn` (the append
to and the paged kernel over the full layers' blocks) and `swa_attn`
(the window layers' read of the slot's ring and its attention) over the
time of the `jit_decode_chunk_*` programs that hold them."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._window_full_common import scopes

    if ctx.get("plane") != "serve":
        return None
    sc = scopes(ctx)
    parts = [sc.get(k, 0.0) for k in ("full_attn", "swa_attn")] if sc else []
    if not sc or not sc.get("programs_s") or not all(parts):
        return None
    return 100.0 * sum(parts) / sc["programs_s"]
