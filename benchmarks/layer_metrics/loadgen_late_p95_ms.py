"""How late the load generator sent a request, against its schedule: a
starved generator must not read as a fast server."""
LAYER, UNIT, SOURCE, MOVES = "launcher and load generator", "ms", "host_clock", "request_p95_ms"


def read(ctx):
    from benchmarks.loadgen import percentile

    if ctx.get("plane") != "serve" or ctx["traffic"]["kind"] != "open_loop":
        return None
    return percentile(ctx["client"]["late_ms"], 95)
