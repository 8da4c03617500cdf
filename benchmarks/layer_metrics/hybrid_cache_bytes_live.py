"""What the two cache kinds hold together for the live batch: a tick's
live tokens x the bytes a token costs in the paged K and V pools, plus
its live rows x the bytes a slot's per-slot leaves cost (both from the
engine's `stats()`: `cache_bytes_per_token`, `cache_bytes_per_slot`),
mean over the ring's ticks that dispatched a chunk.  Nothing where
either kind costs nothing: a model of one kind."""
LAYER, UNIT, SOURCE, MOVES = "engine", "bytes", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_hybrid as rl
    from benchmarks.layer_metrics._common import mean
    from benchmarks.layer_metrics._hybrid_common import hybrid_ticks

    if ctx.get("plane") != "serve":
        return None
    held = []
    for r in ctx.get("replicas", []):
        per_token = r.get("engine", {}).get("cache_bytes_per_token")
        per_slot = r.get("engine", {}).get("cache_bytes_per_slot")
        if not per_token or not per_slot:
            continue
        held += [rl.cache_bytes(t["live_tokens"], t["state_rows_live"],
                                per_token, per_slot)
                 for t in hybrid_ticks({"replicas": [r]})]
    return mean(held)
