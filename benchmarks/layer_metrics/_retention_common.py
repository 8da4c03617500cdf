"""What the retention readers share: the device time the trace puts
under the program's `retention_attn` scope inside the decode programs
(`benchmarks/trace_scopes.py`, kept by the plane as `trace["scopes"]`),
and the tick ring's live-row counters.  A program without the scope or
the counters (the parent of the PR that added them) yields nothing
here, and the readers then return None."""


def scopes(ctx):
    """Summed over the traced chips; None without a decode program."""
    found = [t["scopes"] for t in
             (r.get("trace", {}) for r in ctx.get("replicas", []))
             if t.get("scopes", {}).get("program_calls")]
    if not found:
        return None
    keys = set().union(*found)
    return {k: sum(f.get(k, 0.0) for f in found) for k in keys}


def state_ticks(ctx):
    """Tick records of a per-slot cache that dispatched a chunk."""
    return [t for r in ctx.get("replicas", []) for t in r.get("tick_ring", [])
            if t.get("state_rows_live") and t.get("row_steps")]
