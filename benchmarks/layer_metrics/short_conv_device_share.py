"""Share of the decode programs' device time spent in the gated short
convolutions: the trace's time under the scope `short_conv` (the input
projection, the gate products, the three taps on the slot's rolling
state, the state's write-back, the output projection) over the time of
the `jit_decode_chunk_*` programs that hold it.  Small: the operator is
two projections and 147 KB of state a row."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._moe_common import scopes

    if ctx.get("plane") != "serve":
        return None
    sc = scopes(ctx)
    if not sc or not sc.get("programs_s") or not sc.get("short_conv"):
        return None
    return 100.0 * sc["short_conv"] / sc["programs_s"]
