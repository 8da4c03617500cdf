"""What the `afmoe_*` readers share: they read the train plane's run of
a configuration whose plane is `train_window_moe` and return None on
anything else (another plane, GPT-2's cell, a run without a trace where
they need one, a program without the scopes or counters)."""

PLANE = "train_window_moe"


def mine(ctx, traced=False):
    """The train context of one of this plane's cells, else None."""
    if (ctx.get("plane") != "train"
            or ctx.get("config", {}).get("plane") != PLANE
            or "train" not in ctx):
        return None
    if traced and ("peaks" not in ctx
                   or not ctx["train"].get("trace", {}).get("devices")):
        return None
    return ctx["train"]


def kernel_share(ctx, name, work_of):
    """`work_of(calls) -> {"flops", "bytes"}` of ALL the traced steps
    (`calls` of the step's program hold the kernel) over the kernel's
    summed time, as a share of the roofline."""
    from benchmarks import roofline
    from benchmarks.layer_metrics._common import kernel

    if mine(ctx, traced=True) is None:
        return None
    k = kernel(ctx, name)
    if k is None or not k["calls"]:
        return None
    return roofline.share(work_of(k["calls"]), k["op_seconds"], ctx["peaks"])


def held_pairs_a_step(t):
    """Mean held pairs a step: over the traced steps where the loop
    marked any, else over the window."""
    xs = t.get("step_metrics", {}).get("held_pairs") or []
    at = [xs[i] for i in t.get("traced_steps", []) if 0 <= i < len(xs)]
    xs = at or xs
    return sum(xs) / len(xs) if xs else None


def scope_share(ctx, names):
    """The device time under the scopes `names` over the step
    programs' device time, in percent."""
    t = mine(ctx, traced=True)
    sc = t and t["trace"].get("scopes")
    if not sc or not sc.get("programs_s"):
        return None
    part = sum(sc.get(n, 0.0) for n in names)
    return 100.0 * part / sc["programs_s"] if part else None


def expert_work(ctx, work):
    """`work(held pairs of the traced steps, layers, held, dim, inter)`
    as a function of the step program's calls."""
    t = mine(ctx, traced=True)
    pairs = t and held_pairs_a_step(t)
    if not pairs:
        return None
    m = ctx["config"]["model"]
    layers = m["num_hidden_layers"] - m["num_dense_layers"]
    return lambda calls: work(pairs * calls, layers * calls,
                              m["num_experts"], m["hidden_size"],
                              m["moe_intermediate_size"])
