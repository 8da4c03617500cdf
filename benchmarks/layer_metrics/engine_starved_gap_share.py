"""The program's own upper bound on the device's idle share: a tick that
found its previous chunk FINISHED (`starved`) may have let the device
run dry for as long as the host took from the read before to the tick's
first program (`host_gap_s`).  Those gaps, as a share of the ticks' wall
and the loop's blocked time.  By phase in the account's `gap_*_us`
columns; the loop's `wait_us` (nothing live: idle for want of traffic)
is kept apart from it."""
LAYER, UNIT, SOURCE, MOVES = "engine", "%", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._engine_account import share, window_sums

    a = window_sums(ctx)
    if a is None:
        return None
    return share(a["host_gap_us"], a)
