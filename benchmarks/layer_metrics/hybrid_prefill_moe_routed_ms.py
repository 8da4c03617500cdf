"""Device time a packed prefill program spends in the hybrid model's
routed experts: the trace's time under the scope `moe_routed` (the
gather into expert order, the three grouped products, the SwiGLU
between them, the way back) inside the `jit_prefill_packed_*` programs,
over their calls, from `trace["prefill_scopes"]` as the plane keeps it.
Most of a prefill program, and prefill is most of the device where
answers are short.  None where the trace holds no such program or no
such scope."""
LAYER, UNIT, SOURCE, MOVES = "models", "ms", "device_trace", "serve_tokens_per_s"


def read(ctx):
    if ctx.get("plane") != "serve":
        return None
    found = [t["prefill_scopes"] for t in
             (r.get("trace", {}) for r in ctx.get("replicas", []))
             if t.get("prefill_scopes", {}).get("program_calls")]
    routed = sum(f.get("moe_routed", 0.0) for f in found)
    if not routed:
        return None
    return 1e3 * routed / sum(f["program_calls"] for f in found)
