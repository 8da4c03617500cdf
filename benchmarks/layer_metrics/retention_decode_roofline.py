"""The retention kernel that WRITES the state (a decode chunk's last
step, the flush) against its roofline: the least time one call (one
layer) needs for the rows that were LIVE AT THAT STEP, each row's state
and key sum read and written once at the 8,256 distinct monomials a KV
head (34.08 MB a row, roofline_retention.py), over the time the trace
gives a call.  The rows are the engine's own count, `state_rows_flushed`
a tick, averaged over the ring; a program that does not say it (one
that writes at every step) is counted at a tick's live row-steps over
its chunk's steps.  Memory-bound: ~3 flop/B."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_retention as rl
    from benchmarks.layer_metrics._retention_common import state_step_roofline

    return state_step_roofline(ctx, "retention_decode", 1, rl.retention_decode)
