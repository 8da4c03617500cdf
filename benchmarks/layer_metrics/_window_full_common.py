"""What the window-and-full-attention readers share: the device time the
trace puts under the program's `full_attn` / `swa_attn` / `moe_*` scopes
inside the decode programs (kept by the plane as `trace["scopes"]`) and
inside the prefill programs (`trace["prefill_scopes"]`), and the tick
ring's counters of both cache kinds and of the held experts.  A program
without the scopes or the counters (the parent of the PR that added
them, another model's cell) yields nothing here, and the readers then
return None."""

from benchmarks.layer_metrics._moe_common import scopes  # noqa: F401
from benchmarks.layer_metrics._sparse_latent_common import (  # noqa: F401
    live_rows, window_ticks)


def widths(ctx):
    """(model, engine, deployment) of a configuration that has the
    mechanism; None for any other."""
    cfg = ctx.get("config", {})
    m = cfg.get("model", {})
    if "hybrid_layer_pattern" not in m or "deployment" not in cfg:
        return None
    return m, cfg["engine"], cfg["deployment"]


def ticks(ctx):
    """The window's tick records of a dispatched chunk that carry the
    counters of both cache kinds."""
    return [t for t in window_ticks(ctx)
            if t.get("full_cache_tokens_live") and t.get("window_rows_live")
            and t.get("row_steps_live")]


def expert_ticks(ctx):
    """The window's tick records that harvested a chunk's HELD-expert
    counters."""
    return [t for t in window_ticks(ctx)
            if t.get("experts_held") and t.get("expert_load_max")]


def prefill_scopes(ctx):
    """The scopes' device time inside the prefill programs, summed over
    the traced chips; None without such a program."""
    found = [t["prefill_scopes"] for t in
             (r.get("trace", {}) for r in ctx.get("replicas", []))
             if t.get("prefill_scopes", {}).get("program_calls")]
    if not found:
        return None
    keys = set().union(*found)
    return {k: sum(f.get(k, 0.0) for f in found) for k in keys}
