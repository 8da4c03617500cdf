"""What the HOST needs of one engine tick, mean over the window's ticks:
the phases in which the engine's thread works (`plan`, `prefill`,
`dispatch`, `harvest_host`), without its wait for the device
(`device_wait`), which `engine_tick_host_ms` holds too.  From the
engine's per-second account, so an untraced run reads it as well."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._engine_account import window_sums

    a = window_sums(ctx)
    if a is None:
        return None
    busy = (a["plan_us"] + a["prefill_us"] + a["dispatch_us"]
            + a["harvest_host_us"])
    return 1e-3 * busy / a["ticks"]
