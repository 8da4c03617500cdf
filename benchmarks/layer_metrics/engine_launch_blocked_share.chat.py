"""`engine_launch_blocked_share` as read in the open-loop cells, where
the end-to-end metric it should move is the request tail."""
from benchmarks import manifest

LAYER, UNIT, SOURCE, MOVES = "engine", "%", "program_counter", "request_p95_ms"


def read(ctx):
    return manifest.layer_metric("engine_launch_blocked_share").read(ctx)
