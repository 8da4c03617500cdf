"""What the recurrent-state readers share: the device time the trace
puts under the program's `ssm_*` / `latent_moe_*` / `moe_shared` scopes
inside the decode programs (kept by the plane as `trace["scopes"]`) and
inside the prefill programs (`trace["prefill_scopes"]`), and the tick
ring's counters of the states and of the held experts.  A program
without the scopes or the counters (the parent of the PR that added
them, another model's cell) yields nothing here, and the readers then
return None."""

from benchmarks.layer_metrics._moe_common import scopes  # noqa: F401
from benchmarks.layer_metrics._sparse_latent_common import (  # noqa: F401
    live_rows, window_ticks)
from benchmarks.layer_metrics._window_full_common import (  # noqa: F401
    prefill_scopes)

PREFILLS = ("prefill_chunk_n", "prefill_packed_n")


def widths(ctx):
    """(model, engine, deployment) of a configuration that has the
    mechanism; None for any other."""
    cfg = ctx.get("config", {})
    m = cfg.get("model", {})
    if "ssm_state_size" not in m or "deployment" not in cfg:
        return None
    return m, cfg["engine"], cfg["deployment"]


def decode_steps(ctx, sc):
    """Decode steps the traced decode programs ran: the paged decode
    kernel's calls over the attention layers (one call a layer and
    step), so that a program the trace's edge CUT counts the steps the
    trace holds of it and not a whole chunk; a trace without the kernel:
    the programs' calls x the chunk."""
    from benchmarks.layer_metrics._common import kernel

    m, e, _ = widths(ctx)
    found, layers = kernel(ctx, "paged_decode"), \
        m["hybrid_override_pattern"].count("*")
    if found and found.get("op_calls") and layers:
        return found["op_calls"] / layers
    return sc["program_calls"] * e["chunk"]


def mamba_layers(m) -> int:
    return m["hybrid_override_pattern"].count("M")


def state_ticks(ctx):
    """The window's tick records of a dispatched chunk that carry the
    state's counter."""
    return [t for t in window_ticks(ctx)
            if t.get("ssm_bytes_live") and t.get("row_steps_live")]


def expert_ticks(ctx):
    """The window's tick records that harvested a chunk's HELD-expert
    counters, `held_pairs` among them."""
    return [t for t in window_ticks(ctx)
            if t.get("experts_held") and t.get("expert_load_max")
            and "held_pairs" in t]
