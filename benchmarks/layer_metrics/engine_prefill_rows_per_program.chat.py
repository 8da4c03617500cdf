"""`engine_prefill_rows_per_program` as read in the open-loop cells, where
the end-to-end metric it should move is the request tail (a cell below
the knee is judged on its tail, not on the tokens it was offered)."""
from benchmarks import manifest

LAYER, UNIT, SOURCE, MOVES = "engine", "ratio", "program_counter", "request_p95_ms"


def read(ctx):
    return manifest.layer_metric("engine_prefill_rows_per_program").read(ctx)
