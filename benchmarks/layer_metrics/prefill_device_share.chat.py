"""`prefill_device_share` as read in the open-loop cells, where the end-to-end
metric it should move is the request tail (a cell below the knee is
judged on its tail, not on the tokens it was offered)."""
from benchmarks import manifest

LAYER, UNIT, SOURCE, MOVES = "models", "%", "device_trace", "request_p95_ms"


def read(ctx):
    return manifest.layer_metric("prefill_device_share").read(ctx)
