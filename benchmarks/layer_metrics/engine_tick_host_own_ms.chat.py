"""`engine_tick_host_own_ms` as read in the open-loop cells, where the
end-to-end metric it should move is the request tail."""
from benchmarks import manifest

LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", "request_p95_ms"


def read(ctx):
    return manifest.layer_metric("engine_tick_host_own_ms").read(ctx)
