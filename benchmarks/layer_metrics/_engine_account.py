"""What the engine-account readers share: the engine's own per-second
account of its ticks (`stats()["tick_account"]`: the tick records
summed by the wall second a tick began in, `LlamaEngine` keeps it and
every serve plane ships `stats()` whole), cut to the measured window
and pooled over the replicas.  A program that keeps no such account
(the parent of the PR that added it) yields None here, and the readers
then return None."""

import math
import os

from benchmarks.layer_metrics._engine_phases import window_records


def window(ctx):
    """(start, end) of the measured window on the wall clock: where the
    benchmark's own `setup_s` ends (`RT_BENCH_T0`, the guard's stamp of
    the run's start, is what `setup_s` is counted from), for the cell's
    seconds.  Without the stamp, APPROXIMATELY: from the earliest submit
    among the window's lifecycle records (the first request lands a
    little after the window opens).  None where neither is there."""
    t0 = os.environ.get("RT_BENCH_T0")
    if t0:
        start = float(t0) + ctx["setup_s"]
    else:
        submits = [r["t_done"] - r["first_token_s"] - r["decode_s"]
                   for r in window_records(ctx)]
        if not submits:
            return None
        start = min(submits)
    return start, start + ctx["seconds"]


def window_sums(ctx):
    """The account's columns summed over the seconds that lie WHOLLY in
    the window (a second that straddles its start holds warm-up's ticks
    too), over every replica; None without an account or a window, or
    where no such second had a tick."""
    accounts = [r["engine"]["tick_account"] for r in ctx.get("replicas", [])
                if r.get("engine", {}).get("tick_account")]
    span = window(ctx) if accounts else None
    if span is None:
        return None
    first, last = math.ceil(span[0]), math.floor(span[1]) - 1
    out = {}
    for acct in accounts:
        for row in acct["rows"]:
            if first <= row[0] <= last:
                for name, v in zip(acct["fields"][1:], row[1:]):
                    out[name] = out.get(name, 0) + v
    return out if out.get("ticks") else None


def share(part, sums):
    """`part` microseconds as a share (%) of what the loop's thread
    lived through: the ticks' wall and its blocked time between them."""
    return 100.0 * part / (sums["tick_us"] + sums["wait_us"])
