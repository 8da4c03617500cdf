"""`decode_step_ms` as read in the open-loop cells, where the end-to-end
metric it should move is the request tail (a cell below the knee is
judged on its tail, not on the tokens it was offered)."""
from benchmarks import manifest

LAYER, UNIT, SOURCE, MOVES = "models", "ms", "device_trace", "request_p95_ms"


def read(ctx):
    return manifest.layer_metric("decode_step_ms").read(ctx)
