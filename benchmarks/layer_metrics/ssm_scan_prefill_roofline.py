"""The Mamba-2 layers' scan in ADMISSION against its roofline: the
device time the trace puts under the `ssm_scan` scope inside the prefill
programs (`jit_prefill_packed_*`, `jit_prefill_chunk_*`), against the
least time the chip needs for the recurrence of the positions those
programs held, counted in its recurrent form (roofline_nemotron_h.py: 5
operations a position and state element, a program's float32 state in
and out once), in each Mamba layer.  The positions are the engine's own
count, launch by launch (`stats()["launch_account"]`, the rows a
profiler session recorded; `_launch_account.held_by_the_traced_calls`),
so the work is that of the TRACED span; where the traced launches and
the trace's calls of those programs differ by more than a tick's
launches, the rows the trace's own programs hold by their names
(`padded_tokens`)."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "serve_tokens_per_s"


def padded_tokens(trace):
    """Rows of the admission programs the trace saw, from their names
    (`jit_prefill_chunk_n<N>(<hash>)`: N rows a call), padding with
    them: what the share falls back on where the launch account and the
    trace cannot be joined (this mix's prompts are whole blocks and
    chunks: `engine_prefill_padding_share` 0)."""
    from benchmarks.layer_metrics import _recurrent_common as c

    total = 0
    for name, calls in (trace.get("module_calls") or {}).items():
        if name.startswith(tuple("jit_" + p for p in c.PREFILLS)):
            total += calls * int(name.split("_n")[-1].split("(")[0])
    return total


def read(ctx):
    from benchmarks import roofline_nemotron_h as rl
    from benchmarks.layer_metrics import _launch_account as la
    from benchmarks.layer_metrics import _recurrent_common as c

    if ctx.get("plane") != "serve" or "peaks" not in ctx or not c.widths(ctx):
        return None
    tokens = programs = seconds = 0.0
    for r in ctx.get("replicas", []):
        held = la.held_by_the_traced_calls(r, c.PREFILLS, "tokens")
        sc = (r.get("trace") or {}).get("prefill_scopes") or {}
        if held is None:  # no join: the rows the trace's programs hold
            held = padded_tokens(r.get("trace") or {}) or None
        if held is None or not sc.get("ssm_scan"):
            continue
        tokens += held
        programs += sc["program_calls"]
        seconds += sc["ssm_scan"]
    if not tokens or not seconds:
        return None
    m, _, _ = c.widths(ctx)
    work = rl.ssm_scan(tokens, programs, m["mamba_num_heads"],
                       m["mamba_head_dim"], m["n_groups"],
                       m["ssm_state_size"])
    work = {k: v * c.mamba_layers(m) for k, v in work.items()}
    return rl.share(work, seconds, ctx["peaks"])
