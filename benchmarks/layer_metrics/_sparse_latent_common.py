"""What the selecting-latent-attention readers share: the device time
the trace puts under the program's `dsa_*` / `swa_attn` / `moe_*` scopes
inside the decode programs (kept by the plane as `trace["scopes"]`),
and the tick ring's selection, window and held-expert counters.  A
program without the scopes or the counters (the parent of the PR that
added them, another model's cell) yields nothing here, and the readers
then return None."""

from benchmarks.layer_metrics._engine_account import window
from benchmarks.layer_metrics._moe_common import scopes  # noqa: F401


def window_ticks(ctx):
    """The ring's ticks that began inside the measured window (set-up's
    ticks hold short warm-up contexts); all of them where a tick has no
    stamp or the window is not known."""
    out = [t for r in ctx.get("replicas", []) for t in r.get("tick_ring", [])]
    span = window(ctx) if "setup_s" in ctx else None
    if span is None or not all("t_wall" in t for t in out):
        return out
    return [t for t in out if span[0] <= t["t_wall"] < span[1]]


def ticks(ctx):
    """The window's tick records of a dispatched chunk that carry the
    selection's counter."""
    return [t for t in window_ticks(ctx)
            if t.get("dsa_selected_share") and t.get("row_steps_live")]


def expert_ticks(ctx):
    """The window's tick records that harvested a chunk's HELD-expert
    counters."""
    return [t for t in window_ticks(ctx)
            if t.get("experts_held") and t.get("expert_load_max")]


def widths(ctx):
    """(model, engine, deployment) of a configuration that has the
    mechanism; None for any other."""
    cfg = ctx.get("config", {})
    m = cfg.get("model", {})
    if "index_topk" not in m or "deployment" not in cfg:
        return None
    return m, cfg["engine"], cfg["deployment"]


def steps_and_layers(ctx, sc, kind):
    """(decode steps the traced programs ran, layers of `kind`)."""
    m, e, _ = widths(ctx)
    return (sc["program_calls"] * e["chunk"],
            sum(t == kind for t in m["layer_types"]))


def live_rows(t, chunk):
    """A tick's mean live rows a step."""
    return t["row_steps_live"] / chunk
