"""What the expert-layer readers share: the device time the trace puts
under the program's `moe_*` scopes inside the decode programs
(`benchmarks/trace_scopes.py`, kept by the plane as `trace["scopes"]`),
and the tick ring's expert counters.  A program without the scopes or
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


def expert_ticks(ctx):
    """Tick records that harvested a chunk's expert counters."""
    return [t for r in ctx.get("replicas", []) for t in r.get("tick_ring", [])
            if t.get("experts_total") and t.get("expert_load_max")]
