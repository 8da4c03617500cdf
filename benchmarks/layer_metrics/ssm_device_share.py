"""Share of the decode programs' device time spent in the Mamba-2
layers: the trace's time under the scopes `ssm_step` (the state's read
and write), `ssm_conv` and `ssm_proj` (in_proj, gated norm, out_proj)
over the time of the `jit_decode_chunk_*` programs that hold them."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._recurrent_common import scopes

    if ctx.get("plane") != "serve":
        return None
    sc = scopes(ctx)
    if not sc or not sc.get("programs_s") or not sc.get("ssm_step"):
        return None
    return 100.0 * sum(sc.get(k, 0.0) for k in (
        "ssm_step", "ssm_conv", "ssm_proj")) / sc["programs_s"]
