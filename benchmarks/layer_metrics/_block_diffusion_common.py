"""What the block-diffusion readers share: the tick ring's counters of
ROW-FORWARDS by kind (a chunk of this engine model is `chunk` forwards,
and a live row's forward commits a block of `B` tokens or decides some
of its positions), the ticks that lie in the traced span, and the
device time the trace puts under the program's scopes inside the decode
programs.  A program without the counters or the scopes (the parent of
the PR that added them, another model's cell) yields nothing here, and
the readers then return None."""

from benchmarks.layer_metrics._moe_common import scopes  # noqa: F401
from benchmarks.layer_metrics._sparse_latent_common import window_ticks


def widths(ctx):
    """(model, engine, assumed) of a configuration that generates by
    diffusion over blocks; None for any other."""
    cfg = ctx.get("config", {})
    if "block_length" not in cfg.get("assumed", {}):
        return None
    return cfg["model"], cfg["engine"], cfg["assumed"]


def _counted(ticks):
    return [t for t in ticks if t.get("row_steps_live")
            and "commit_row_steps" in t]


def ticks(ctx):
    """The window's tick records that harvested a chunk's counts."""
    return _counted(window_ticks(ctx))


def traced_ticks(ctx):
    """The counted ticks that began inside the span the trace ran in
    (`trace["wall_span"]`, the plane's stamp on the ring's clock), so
    that a kernel's traced time is set against the work of the SAME
    seconds; the one nearest the span where none began inside it; the
    window's where the trace has no span."""
    spans = [t["wall_span"] for t in
             (r.get("trace", {}) for r in ctx.get("replicas", []))
             if t.get("wall_span")]
    mine = ticks(ctx)
    if not spans or not all("t_wall" in t for t in mine):
        return mine
    lo, hi = min(s[0] for s in spans), max(s[1] for s in spans)
    inside = [t for t in mine if lo <= t["t_wall"] <= hi]
    if inside or not mine:
        return inside
    return [min(mine, key=lambda t: abs(t["t_wall"] - (lo + hi) / 2))]


def forwards(ctx, sc):
    """Forwards the traced decode programs ran."""
    return sc["program_calls"] * widths(ctx)[1]["chunk"]
