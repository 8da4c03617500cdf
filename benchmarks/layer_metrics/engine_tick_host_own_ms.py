"""What the HOST ITSELF needs of one engine tick, mean over the window's
ticks: the phases in which the engine's thread works (`plan`, `prefill`,
`dispatch`, `harvest_host`, as `engine_tick_host_busy_ms` sums them)
LESS the time it was blocked inside its launches (`launch_blocked_us`:
the device's queue was full).  With one or two programs a tick the two
read the same; with many, this is the one that says whether the host is
on the path."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._launch_account import (HOST_PHASES,
                                                          launch_sums)

    a = launch_sums(ctx)
    if a is None:
        return None
    own = sum(a[k] for k in HOST_PHASES) - a["launch_blocked_us"]
    return 1e-3 * own / a["ticks"]
