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


def rows_a_call(ctx):
    """Mean live rows a kernel call walks, (a reading step's, a writing
    step's), from the ticks that dispatched a chunk of `chunk` steps.
    Where the trace holds the kernel that only reads, a chunk's last
    step alone writes, the rows live AT IT (`state_rows_flushed`), and
    its other steps read the rest of the live row-steps.  A program
    that writes at every step, or does not say what it flushed, walks
    the chunk's mean at every call."""
    from benchmarks.layer_metrics._common import kernel, mean

    chunk = ctx["config"]["engine"]["chunk"]
    ticks = state_ticks(ctx)
    if not ticks:
        return None
    if not (kernel(ctx, "retention_read") and chunk > 1
            and all("state_rows_flushed" in t for t in ticks)):
        rows = mean(t["row_steps_live"] / chunk for t in ticks)
        return rows, rows
    return (mean((t["row_steps_live"] - t["state_rows_flushed"])
                 / (chunk - 1) for t in ticks),
            mean(t["state_rows_flushed"] for t in ticks))


def state_step_roofline(ctx, name, step, count):
    """Kernel `name` against its roofline: `count(rows, heads, kv_heads,
    head_dim)` of `roofline_retention.py` at the live rows a call of
    that `step` (0 a reading one, 1 a writing one) walks, over the time
    the trace gives a call.  None without the kernel or the rows."""
    from benchmarks import roofline_retention as rl
    from benchmarks.layer_metrics._common import kernel

    if ctx.get("plane") != "serve" or "peaks" not in ctx:
        return None
    k, rows = kernel(ctx, name), rows_a_call(ctx)
    if not k or not rows or not rows[step]:
        return None
    m = ctx["config"]["model"]
    work = count(rows[step], m["num_attention_heads"],
                 m["num_key_value_heads"], m["head_dim"])
    return rl.share(work, k["op_seconds"] / k["op_calls"], ctx["peaks"])
