"""What the two cache kinds hold together for the live batch: a tick's
live tokens x the bytes a token costs in the FULL layers' paged K and V
pools, plus its live rows x the bytes a slot's rings cost over the
window layers (both from the engine's `stats()`:
`cache_bytes_per_token`, `cache_bytes_per_slot`), mean over the window's
ticks that dispatched a chunk.  Nothing where the configuration has no
window layers on a ring."""
LAYER, UNIT, SOURCE, MOVES = "engine", "bytes", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_window_full as rl
    from benchmarks.layer_metrics import _window_full_common as c
    from benchmarks.layer_metrics._common import mean

    if ctx.get("plane") != "serve" or not c.widths(ctx):
        return None
    held = []
    for r in ctx.get("replicas", []):
        per_token = r.get("engine", {}).get("cache_bytes_per_token")
        per_slot = r.get("engine", {}).get("cache_bytes_per_slot")
        if not per_token or not per_slot:
            continue
        held += [rl.cache_bytes(t["live_tokens"], t["state_rows_live"],
                                per_token, per_slot)
                 for t in c.ticks({**ctx, "replicas": [r]})]
    return mean(held)
