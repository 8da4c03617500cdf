"""Device time an admission program spends in this chip's share of the
routed experts: the trace's time under the scope `moe_routed` (the held
pairs' gather into expert order, the three grouped products, the SwiGLU
between them, the way back; six expert layers a program) inside the
`jit_prefill_packed_*` and `jit_prefill_chunk_*` programs, over their
calls, from `trace["prefill_scopes"]` as the plane keeps it.  The
largest scope of the program that is most of the device where prompts
are long.  None where the trace holds no such program or no such
scope."""
LAYER, UNIT, SOURCE, MOVES = "models", "ms", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._window_full_common import prefill_scopes

    if ctx.get("plane") != "serve":
        return None
    sc = prefill_scopes(ctx)
    if not sc or not sc.get("moe_routed"):
        return None
    return 1e3 * sc["moe_routed"] / sc["program_calls"]
