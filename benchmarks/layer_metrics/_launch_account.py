"""What the readers of the engine's LAUNCH stamp share: the three columns
it appends to `stats()["tick_account"]` (`launches`, `launch_us`,
`launch_blocked_us`: a launch that took longer than the engine's
`LAUNCH_BLOCKED_S` found the device's queue full and blocked, which is
the device's time inside `prefill_us` / `dispatch_us`), cut to the window
as `_engine_account.window_sums` cuts the others, and
`stats()["launch_account"]`, the launches summed by program name and by
whether a profiler session recorded them, joined to the trace's own
count of the same programs (`jit_<program>(<hash>)`).  A program that
keeps neither (the parent of the PR that added them) yields None here,
and the readers then return None."""

from benchmarks.layer_metrics._engine_account import window_sums

HOST_PHASES = ("plan_us", "prefill_us", "dispatch_us", "harvest_host_us")
# a tick launches its programs in one burst and a session holds a few
# ticks, so the traced launches and the trace's calls of the same
# programs differ by up to one tick's launches (36 against 44 on the
# chip at 0.5 s a tick and 3 s a session); past this share of the calls
# the two are not the same span
EDGE_SHARE = 0.25


def launch_sums(ctx):
    """The window's sums, of an account that has the stamp's columns."""
    a = window_sums(ctx)
    return a if a is not None and "launch_blocked_us" in a else None


def traced_rows(replica, prefixes):
    """{program: {field: value}} of the `launch_account` rows of one
    replica that a profiler session recorded and whose program's name
    starts with one of `prefixes`; None without such an account or such
    a row."""
    acct = replica.get("engine", {}).get("launch_account")
    if not acct:
        return None
    rows = (dict(zip(acct["fields"], r)) for r in acct["rows"])
    return {r["program"]: r for r in rows
            if r["traced"] and r["program"].startswith(prefixes)} or None


def trace_calls(trace, program):
    """How often the trace saw the program `jit_<program>(<hash>)` run."""
    return sum(n for name, n in (trace.get("module_calls") or {}).items()
               if name.startswith("jit_" + program + "("))


def held_by_the_traced_calls(replica, prefixes, field):
    """`field` of what the trace's calls of the programs `prefixes` held:
    program by program, the traced launches' mean times the trace's own
    count of that program's calls (the launches run a queue's depth ahead
    of the device, so the two spans hold the same programs in the same
    mix, not the same calls).  None where there is nothing to join, or
    where launches and calls differ by more than EDGE_SHARE of the
    calls."""
    trace = replica.get("trace") or {}
    held = traced_rows(replica, prefixes)
    if not trace.get("devices") or held is None:
        return None
    calls = {p: trace_calls(trace, p) for p in held}
    seen, launched = sum(calls.values()), sum(
        r["launches"] for r in held.values())
    if not seen or abs(launched - seen) > EDGE_SHARE * seen:
        return None
    return sum(r[field] / r["launches"] * calls[p] for p, r in held.items())
