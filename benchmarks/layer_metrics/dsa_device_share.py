"""Share of the decode programs' device time spent selecting and
attending in the full layers: the trace's time under the scopes
`dsa_index` (scoring a row's whole context), `dsa_select` (the top-k)
and `dsa_attn` (gathering the selected rows and attending them) over
the time of the `jit_decode_chunk_*` programs that hold them."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._sparse_latent_common import scopes

    if ctx.get("plane") != "serve":
        return None
    sc = scopes(ctx)
    parts = [sc.get(k, 0.0) for k in ("dsa_index", "dsa_select",
                                      "dsa_attn")] if sc else []
    if not sc or not sc.get("programs_s") or not sum(parts):
        return None
    return 100.0 * sum(parts) / sc["programs_s"]
