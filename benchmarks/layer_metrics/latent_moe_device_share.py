"""Share of the decode programs' device time spent in the latent expert
layers: the trace's time under the scopes `moe_router`,
`latent_moe_proj` (`W_in`, `W_out`), `latent_moe_routed` (the held
experts' grouped products and the way back) and `moe_shared` over the
time of the `jit_decode_chunk_*` programs that hold them."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._recurrent_common import scopes

    if ctx.get("plane") != "serve":
        return None
    sc = scopes(ctx)
    if not sc or not sc.get("programs_s") or not sc.get("latent_moe_routed"):
        return None
    return 100.0 * sum(sc.get(k, 0.0) for k in (
        "moe_router", "latent_moe_proj", "latent_moe_routed",
        "moe_shared")) / sc["programs_s"]
