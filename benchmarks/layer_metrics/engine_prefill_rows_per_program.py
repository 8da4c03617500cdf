"""Requests prefilled per prefill program the engine dispatched, over
the window's finished requests: each lifecycle record carries
`prefill_rows`, the requests its prefill program held, so a program of
r rows shows as r records of 1/r each.  Above 1 a tick's admissions
shared one read of the weights; 1 is one program a request.  None where
the records keep no `prefill_rows` (the parent of the PR that packed
admission's prefills)."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ratio", "program_counter", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._engine_phases import window_records

    if ctx.get("plane") != "serve":
        return None
    rows = [q.get("prefill_rows") for q in window_records(ctx)]
    if not rows or not all(rows):
        return None
    return len(rows) / sum(1.0 / r for r in rows)
