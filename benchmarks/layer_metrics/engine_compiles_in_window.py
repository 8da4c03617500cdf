"""Backend compilations inside the measured window, counted by a
`jax.monitoring` listener in the process that owns the chip.  Should be
0: every shape is warmed in set-up."""
LAYER, UNIT, SOURCE, MOVES = "engine", "count", "program_counter", "serve_tokens_per_s"


def read(ctx):
    if ctx.get("plane") != "serve":
        return None
    return float(sum(len(r["compiles_in_window"]) for r in ctx["replicas"]))
