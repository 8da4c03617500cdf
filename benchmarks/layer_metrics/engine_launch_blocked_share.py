"""How much of the engine loop's time the host spent BLOCKED INSIDE ITS
LAUNCHES: the whole time of every call that handed a program to the
device and took longer than the engine's `LAUNCH_BLOCKED_S` to return
(the device's queue was full: the call waited for a program ahead of
it), as a share of the ticks' wall and the loop's blocked time between
them, over the whole window.  It is a part of `prefill_us` /
`dispatch_us`, which `engine_tick_host_busy_ms` books as the host's
work; beside `engine_device_wait_share` it is the rest of what the loop
waits for the device."""
LAYER, UNIT, SOURCE, MOVES = "engine", "%", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._engine_account import share
    from benchmarks.layer_metrics._launch_account import launch_sums

    a = launch_sums(ctx)
    if a is None:
        return None
    return share(a["launch_blocked_us"], a)
