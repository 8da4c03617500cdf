"""`engine_batch_occupancy` as read in the open-loop cells, where the end-to-end
metric it should move is the request tail (a cell below the knee is
judged on its tail, not on the tokens it was offered)."""
from benchmarks import manifest

LAYER, UNIT, SOURCE, MOVES = "engine", "%", "program_counter", "request_p95_ms"


def read(ctx):
    return manifest.layer_metric("engine_batch_occupancy").read(ctx)
