"""The engine's own time to first token (submit -> first harvested
token), p90 over its trailing window, polled once a second during the
run; the median of the polls.  The client cannot see a first token."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", "request_p95_ms"


def read(ctx):
    from benchmarks.layer_metrics._common import med

    if ctx.get("plane") != "serve":
        return None
    polls = [v * 1e3 for r in ctx["replicas"]
             for v in r.get("ttft_p90_polls_s", []) if v > 0]
    return med(polls)
