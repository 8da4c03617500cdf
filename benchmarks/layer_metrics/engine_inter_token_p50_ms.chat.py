"""`engine_inter_token_p50_ms` as read in the open-loop cells, where the end-to-end
metric it should move is the request tail (a cell below the knee is
judged on its tail, not on the tokens it was offered)."""
from benchmarks import manifest

LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", "request_p95_ms"


def read(ctx):
    return manifest.layer_metric("engine_inter_token_p50_ms").read(ctx)
