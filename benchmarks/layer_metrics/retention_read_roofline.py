"""The retention kernel that only READS the state (every step of a
decode chunk but its last) against its roofline: the least time one
call (one layer, one step) needs for the rows that were LIVE, each
row's state and key sum read once (34.08 MB a row,
roofline_retention.py), over the time the trace gives a call.  Live
rows a call are the engine's own count: a tick's live row-steps less
the rows its last step flushed, over the chunk's other steps, averaged
over the ring.  Memory-bound: ~2.5 flop/B.  None where the trace holds
no such kernel (a program that writes the state at every token)."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import roofline_retention as rl
    from benchmarks.layer_metrics._retention_common import state_step_roofline

    return state_step_roofline(ctx, "retention_read", 0, rl.retention_read)
