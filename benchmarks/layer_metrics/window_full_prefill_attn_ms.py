"""Device time a prefill program spends in attention of either kind: the
trace's time under the scopes `full_attn` (a chunk's rows into the
request's blocks, then the walk through the table key block by key
block; a packed row's segments under their mask) and `swa_attn` (the
banded window attention over ring and rows) inside the
`jit_prefill_packed_*` and `jit_prefill_chunk_*` programs, over their
calls, from `trace["prefill_scopes"]` as the plane keeps it.  None where
the trace holds no such program or no such scope."""
LAYER, UNIT, SOURCE, MOVES = "models", "ms", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._window_full_common import prefill_scopes

    if ctx.get("plane") != "serve":
        return None
    sc = prefill_scopes(ctx)
    if not sc or not sc.get("full_attn") or not sc.get("swa_attn"):
        return None
    return 1e3 * (sc["full_attn"] + sc["swa_attn"]) / sc["program_calls"]
