"""The fused prefill-attention kernel of a FULL layer (keys 192, values
128, `ops/prefill_attention.py`) against its roofline: the operations
the causal mask NEEDS for the rows the traced prefill launches held,
`2 x 64 query heads x (192 + 128)` a (query, key) pair
(roofline_prefill_attention.py) in each of the model's full layers, at
the chip's bf16 peak, over the trace's seconds of the ops named
`prefill_attention*`.  The pairs are the engine's own count, launch by
launch (`stats()["launch_account"]`, the rows a profiler session
recorded, of the programs `prefill_chunk_n*` and `prefill_packed_n*`:
a part of `n` rows behind `lo` keys holds `n * lo + n (n + 1) / 2`),
program by program the traced launches' mean times the trace's calls
(`_launch_account.held_by_the_traced_calls`), so the work is that of
the TRACED span and not the window's mean.  None where the account's
traced launches of those programs and the trace's calls of them differ
by more than a tick's launches.  Compute-bound; needed operations
only."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"

PROGRAMS = ("prefill_chunk_n", "prefill_packed_n")
KERNEL = "prefill_attention"


def read(ctx):
    from benchmarks import roofline_prefill_attention as rl
    from benchmarks.layer_metrics import _launch_account as la
    from benchmarks.layer_metrics._window_full_common import widths

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not widths(ctx):
        return None
    pairs = seconds = 0
    for r in ctx.get("replicas", []):
        held = la.held_by_the_traced_calls(r, PROGRAMS, "attended_pairs")
        if held is None:
            continue
        pairs += held
        seconds += sum(s for n, s in r["trace"].get("op_seconds", {}).items()
                       if n.lstrip("%").startswith(KERNEL))
    if not pairs or not seconds:
        return None
    m, _, _ = widths(ctx)
    work = rl.causal_prefill(
        pairs, m["num_attention_heads"], m["head_dim"], m["v_head_dim"],
        layers=m["hybrid_layer_pattern"].count(0))
    return rl.share(work, seconds, ctx["peaks"])
