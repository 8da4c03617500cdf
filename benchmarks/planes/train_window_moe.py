"""The train plane for ONE CHIP'S SHARE of a sparse model with window
and full attention (`ray_tpu/models/afmoe.py`), in the shape of
`planes/train.py`: `JaxTrainer` with one worker holding the chip, a
fresh host batch every step, `train.report` every step, the same loop
(`ahead_steps` steps in flight, the window closed by waiting for every
step sent), the same `spans`, `ends_s`, memory and trace keys, and
`"plane": "train"` in what `run` returns, so that the train readers of
`layer_metrics/` read it unchanged.

What differs is the model and what `correct` holds it to:

- the step carries a state no optimizer touches (the router's bias);
  before anything is timed, the loss and the WHOLE gradient of
  `afmoe.loss_fn` (held experts, router, gates, head norms, the
  slice's embedding and head) are held to the plain reference on one
  seeded sequence at the timed length, in the routing the window runs
  in: with the balanced bias the window starts from (non-zero, so the
  pick by `s + b` and the weight by `s` are told apart).  The held
  experts' own leaves are held to the reference beside the whole tree,
  and the pairs the held experts got in the sample to what a balanced
  router sends them, so that their backward is compared on rows;
- the bias after the two warm steps is held to the reference's rule
  (`reference/afmoe.bias_rule`, numpy) applied to the reference's own
  counts on the same batches: the share of its entries that differ by
  more than half a step of the rule (an expert whose load lies within
  a few pairs of the layer's mean may fall on the other side of it in
  bfloat16: the comparison counts those, and the limit is what sound
  seeds read);
- every step reports the counters a user would watch (`held_pairs`,
  `expert_load_max`, `expert_load_mean`, `bias_abs_max`, `grad_norm`)
  beside its loss, and the window's `held_pairs` a token, over all of
  it and over its last steps, is held to the same expectation: a step
  whose held experts went idle is faster, and is NOT correct.

The controls (`--control`, exit 4, must come out NOT correct): `fp8`,
the reference with every matmul operand rounded to float8 in the
program's place; `window_off`, the reference with every layer seeing
its whole prefix.
"""

from __future__ import annotations

import collections
import math
import os
import re
import statistics
import time

from benchmarks.planes.train import ANNOTATIONS, zipf_p

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEED = (("models", "afmoe.py"),)
CONTROLS = ("fp8", "window_off")
SCOPES = ("embed", "attn_window", "attn_full", "dense_mlp", "moe_router",
          "moe_routed", "moe_shared", "lm_head", "optimizer", "router_bias")
STEP_METRICS = ("grad_norm", "held_pairs", "expert_load_max",
                "expert_load_mean", "bias_abs_max")
# the step's program in a trace
PROGRAM_PREFIXES = ("jit_step",)


class Scope(str):
    """A scope as `trace_scopes.scope_seconds` looks for it (`scope in
    stack`), equal also to its forms under differentiation: an op of the
    forward pass under `value_and_grad` is traced under `jvp(attn_full)`,
    one of the backward pass under `transpose(jvp(attn_full))`."""

    def __eq__(self, part):
        return isinstance(part, str) and re.fullmatch(
            r"(?:\w+\()*" + re.escape(str(self)) + r"\)*", part) is not None

    __hash__ = str.__hash__


def kernel_predicates(cfg: dict, mix: dict) -> dict:
    """How the trace prints this model's Pallas kernels.  A
    `tpu_custom_call` is named after whatever wraps it (the
    `pallas_call`'s own name where a `named_scope` stands around it:
    `%flash_fwd_grouped.6`; that name inside a transform's where none
    does: `%transpose_jvp_flash_bwd_dq_grouped__.1`; `%tgmm`), so a call
    is told by that name OR by what it returns, as in `planes/train.py`:

    - the forward gives the output `[B * KV, G, T, hd]` and the rows'
      log-sum-exp as float32 ROWS; the backward pair gives dQ of the
      output's shape, and dK with dV `[B * KV, T, hd]`;
    - a grouped product gives a slab's rows `[rows, I]` or `[rows, D]`;
      megablox's `tgmm` the held matrices' gradient in float32.

    The rooflines' readers take `afmoe_*` (`flash_fwd_roofline` and
    `flash_bwd_roofline` count a GPT-2 call and list GPT-2's cell alone).
    `flash_fwd` / `flash_bwd` are here for `flash_fwd_calls_per_bwd`,
    which every train cell reports: the forward call, and ONE call of a
    layer's backward (dK with dV; dQ's is its twin), so that the ratio
    is 1.0 where the replay reads the kept results and 2.0 where it runs
    the forward again."""
    m = cfg["model"]
    B, T = int(mix["batch"]), int(mix["seq"])
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    D, I, E = m["hidden_size"], m["moe_intermediate_size"], m["num_experts"]
    out = f"bf16[{B * KV},{H // KV},{T},{hd}]"
    kv = f"bf16[{B * KV},{T},{hd}]"
    mean = (B * T * m["num_experts_per_tok"] * E
            // cfg["deployment"]["router_experts"])
    rows = min(-(-2 * mean // 256) * 256,
               -(-B * T * m["num_experts_per_tok"] // 256) * 256)

    def parts(n):
        """(the call's name, what it returns with layouts cut out)."""
        if " custom-call(" not in n or "tpu_custom_call" not in n:
            return "", ""
        head, rest = n.split("=", 1)
        return (head.strip().lstrip("%"),
                re.sub(r"\{[^}]*\}", "", rest.split(" custom-call(")[0]
                       ).strip())

    def named(name, n):
        return re.search(rf"(?:^|[^a-z]){name}(?:[^a-z]|$)", n) is not None

    def fwd(n):
        name, res = parts(n)
        return named("flash_fwd_grouped", name) or (
            res.startswith("(" + out) and f"f32[{B * KV}," in res)

    def bwd_dkv(n):
        name, res = parts(n)
        return (named("flash_bwd_dkv_grouped", name)
                or res.replace(" ", "") == f"({kv},{kv})")

    def bwd(n):
        name, res = parts(n)
        return named("flash_bwd_dq_grouped", name) or res == out or bwd_dkv(n)

    def gmm(n):
        name, res = parts(n)
        return named("grouped_matmul_prefetch", name) or res in (
            f"bf16[{rows},{I}]", f"bf16[{rows},{D}]")

    def tgmm(n):
        name, res = parts(n)
        return named("tgmm", name) or res in (f"f32[{E},{D},{I}]",
                                              f"f32[{E},{I},{D}]")

    return {"afmoe_flash_fwd": fwd, "afmoe_flash_bwd": bwd,
            "afmoe_gmm": gmm, "afmoe_tgmm": tgmm,
            "flash_fwd": fwd, "flash_bwd": bwd_dkv}


def run_model(cfg: dict) -> tuple:
    """(`model` as the reference and the weights read it, held, slice):
    the router's width is the deployment's, the experts made are the
    held ones."""
    m, d = cfg["model"], cfg["deployment"]
    held = (int(d["expert_offset"]), int(m["num_experts"]))
    vocab_slice = (int(d.get("vocab_offset", 0)), int(m["vocab_size"]))
    return {**m, "num_experts": int(d["router_experts"])}, held, vocab_slice


def train_loop(config):
    """`train_loop_per_worker`."""
    t0 = time.perf_counter()
    cfg, mix, opts = config["cfg"], config["mix"], config["opts"]
    seed, seconds = int(config["seed"]), float(config["seconds"])
    if opts.get("rehearse"):
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks import weights_afmoe
    from benchmarks.planes import _common
    from benchmarks.reference import afmoe as ref
    from benchmarks.reference import precision
    from ray_tpu import train
    from ray_tpu.core.accelerators import device_report
    from ray_tpu.models import afmoe

    compiles = _common.count_compiles()
    device = device_report()
    timing = {"jax_start_s": time.perf_counter() - t0}
    # `--mix-set trainer={...}` is the builder's way to try another
    # rate or balancing schedule; no committed mix carries the key
    tr = {**cfg["trainer"], **mix.get("trainer", {})}
    m, held, vocab_slice = run_model(cfg)
    acfg = afmoe.AfmoeConfig(
        vocab_size=cfg["published"]["vocab_size"], hidden=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], layer_types=tuple(m["layer_types"]),
        num_dense_layers=m["num_dense_layers"],
        intermediate=m["intermediate_size"],
        moe_intermediate=m["moe_intermediate_size"],
        num_experts=m["num_experts"], top_k=m["num_experts_per_tok"],
        num_shared_experts=m["num_shared_experts"],
        route_scale=m["route_scale"], sliding_window=m["sliding_window"],
        rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
        mup_enabled=m["mup_enabled"],
        load_balance_coeff=m["load_balance_coeff"], held=held,
        vocab_slice=vocab_slice, attention=tr["attention"],
        logits_dtype=jnp.bfloat16 if tr["logits_dtype"] == "bfloat16"
        else jnp.float32, kernel=bool(tr.get("kernel")))
    params = weights_afmoe.params(
        m, held[1], vocab_slice[1], seed,
        float(cfg["assumed"]["initializer_range"]))
    jax.block_until_ready(params)
    timing["weights_s"] = time.perf_counter() - t0

    batch, seq = int(mix["batch"]), int(mix["seq"])
    rows = vocab_slice[1]
    p = zipf_p(rows, float(mix["zipf_a"]))
    draw = lambda r, n: (vocab_slice[0] + r.choice(  # noqa: E731
        rows, size=(n, seq + 1), p=p)).astype(np.int32)

    # -- the bias a checkpoint would bring --------------------------------
    # Seeded random weights route nearly every token to the same few
    # experts (the post-norms give every position's attention output,
    # an average over a window of tokens, unit size: a component all
    # positions share), and a run from there leaves this chip's experts
    # idle.  A model that is being trained has a bias that balances it:
    # the rule itself, run in set-up at a step that shrinks, on seeded
    # batches, through the program's own forward pass, stands in for it.
    bias = np.asarray(weights_afmoe.zero_bias(m))
    counts_of = jax.jit(lambda pr, t, b: afmoe.backbone(acfg, pr, t, b)[1])
    balance_rng = np.random.default_rng([seed, 0xBA1A])
    balance = {"passes": 0}
    for passes, step_size in tr.get("balance", []):
        for _ in range(int(passes)):
            aux = counts_of(params, jnp.asarray(
                draw(balance_rng, batch)[:, :-1]), jnp.asarray(bias))
            bias = ref.bias_rule(bias, np.asarray(aux["counts"]),
                                 float(step_size))
            balance["passes"] += 1
    aux = jax.device_get(counts_of(params, jnp.asarray(
        draw(balance_rng, batch)[:, :-1]), jnp.asarray(bias)))
    c = np.asarray(aux["counts"], np.float64)
    balance.update(load_max_over_mean=float((c.max(-1) / c.mean(-1)).mean()),
                   held_pairs_per_token=float(
                       np.asarray(aux["held_pairs"]).sum() / (batch * seq)),
                   bias_abs_max=float(np.abs(bias).max()))
    check = {"balance": balance}
    del counts_of
    timing["balance_s"] = time.perf_counter() - t0

    # -- correct, part 1: loss and gradient on a seeded sample, in the
    # routing the window runs in (the balanced bias) --------------------
    rng = np.random.default_rng([seed, 0x7A1])
    sample = jnp.asarray(draw(rng, int(cfg["reference"]["sample"])))
    start_bias = jnp.asarray(bias)
    ref_args = (m, held, vocab_slice)
    ref_fn = jax.jit(lambda pr, t, b: ref.loss_and_grad(pr, t, *ref_args, b))
    control = opts.get("control")
    if control == "window_off":
        sys_fn = jax.jit(lambda pr, t, b: ref.loss_and_grad(
            pr, t, *ref_args, b, window_off=True))
    elif control:
        hook = precision.HOOKS[control]
        sys_fn = jax.jit(lambda pr, t, b: ref.loss_and_grad(
            pr, t, *ref_args, b, hook))
    else:
        def sys_fn(pr, t, b):
            (l, aux), g = jax.value_and_grad(
                lambda q: afmoe.loss_fn(acfg, q, t, b), has_aux=True)(pr)
            return (l, aux["counts"]), g
        sys_fn = jax.jit(sys_fn)

    @jax.jit
    def compare(gs, gr):
        sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))  # noqa: E731
                           for x in jax.tree.leaves(t))
        diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, gs, gr)
        return jnp.sqrt(sq(diff) / sq(gr)), jnp.sqrt(sq(gs)), jnp.sqrt(sq(gr))

    def held_leaves(g):
        return [{k: l[k] for k in ("e_gate", "e_up", "e_down")}
                for l in g["layers"] if "e_gate" in l]

    (l_ref, c_ref), g_ref = ref_fn(params, sample, start_bias)
    (l_sys, c_sys), g_sys = sys_fn(params, sample, start_bias)
    rel, n_sys, n_ref = compare(g_sys, g_ref)
    held_rel, _, held_n_ref = compare(held_leaves(g_sys), held_leaves(g_ref))
    c_ref, c_sys = np.asarray(c_ref), np.asarray(c_sys)
    check.update({
        "loss_ref": float(l_ref), "loss_sys": float(l_sys),
        "loss_abs_diff": abs(float(l_sys) - float(l_ref)),
        "grad_rel_err": float(rel), "grad_norm_sys": float(n_sys),
        "grad_norm_ref": float(n_ref),
        # the held experts' matrices alone: a wrong grouped backward
        # cannot hide behind the rest of the tree
        "held_grad_rel_err": float(held_rel),
        "held_grad_norm_ref": float(held_n_ref),
        # the rows that backward was compared on
        "sample_held_pairs_per_token": float(
            c_sys[:, held[0]:held[0] + held[1]].sum() / sample[:, :-1].size),
        # pairs that went to another expert than the reference's
        "picks_moved_share": float(np.abs(c_sys - c_ref).sum()
                                   / (2.0 * c_ref.sum()))})
    del g_ref, g_sys, ref_fn, sys_fn
    timing["check_s"] = time.perf_counter() - t0

    # -- the step, as a user of the model builds it ---------------------
    opt = optax.chain(
        optax.clip_by_global_norm(tr["clip_norm"]),
        optax.adamw(tr["lr"], b1=tr["b1"], b2=tr["b2"],
                    weight_decay=tr["weight_decay"]))
    step = jax.jit(afmoe.make_train_step(acfg, opt), donate_argnums=(0, 1))
    state = {"params": params, "router_bias": start_bias}
    opt_state = opt.init(params)
    del params
    data_rng = np.random.default_rng([seed, 0xDA7A])

    def host_batch():
        return draw(data_rng, batch)

    # compiled once, ahead of the first call, so that the program's own
    # account of its memory can be read (planes/train.py says why)
    step = step.lower(state, opt_state, jnp.asarray(host_batch())).compile()
    ma = step.memory_analysis()
    program_bytes = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                        + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    # -- correct, part 2: the bias after the two warm steps against the
    # rule on the reference's counts of the same batches ----------------
    counts_fn = jax.jit(lambda pr, t, b: ref.loss(pr, t, *ref_args, b)[1])
    coeff = float(m["load_balance_coeff"])
    bias_ref = np.asarray(state["router_bias"])
    losses, history = [], []
    for _ in range(2):  # two warm steps
        tokens = jnp.asarray(host_batch())
        bias_ref = ref.bias_rule(bias_ref, np.asarray(counts_fn(
            state["params"], tokens, jnp.asarray(bias_ref))), coeff)
        state, opt_state, met = step(state, opt_state, tokens)
        met = {k: float(v) for k, v in jax.device_get(met).items()}
        losses.append(met["loss"])
        history.append(met)
    off = lambda b: float(  # noqa: E731
        (np.abs(b - bias_ref) > coeff / 2).mean())
    check["bias_off_share"] = off(np.asarray(state["router_bias"]))
    # what a step that skipped the rule, or moved the bias the other
    # way on the same counts, would read against the same limit
    check["bias_off_share_if_skipped"] = off(bias)
    check["bias_off_share_if_flipped"] = off(2 * bias - bias_ref)
    check["bias_abs_max_ref"] = float(np.abs(bias_ref).max())
    del counts_fn
    timing["warm_s"] = time.perf_counter() - t0
    timing["compiles_in_setup"] = len(compiles)

    # -- the window -----------------------------------------------------
    trace_dir = os.path.join(opts["bench_dir"], "trace_train")
    trace_at = float(mix.get("trace_at_s", 0.4 * seconds))
    trace_s = float(mix.get("trace_s", 3.0))
    tracing, traced, traced_from, step_s = False, None, 0.0, 0.0
    spans = {k: [] for k in ANNOTATIONS}
    ahead = int(mix.get("ahead_steps", 0))
    sent = collections.deque()  # (metrics, dispatched at) of steps in flight
    ends = []                   # when each step's loss reached the host
    traced_steps = []           # indices (into `history`) of traced steps

    def settle():
        """Wait for the oldest step in flight (planes/train.py's span)."""
        met, at = sent.popleft()
        met = {k: float(v) for k, v in jax.device_get(met).items()}
        t = time.perf_counter()
        spans["step"].append(t - max(at, ends[-1] if ends else at))
        ends.append(t)
        return met

    def report(met):
        tc = time.perf_counter()
        with jax.profiler.TraceAnnotation("report"):
            train.report({"step": len(losses), **met})
        if tracing:
            traced_steps.append(len(history))
        losses.append(met["loss"])
        history.append(met)
        spans["report"].append(time.perf_counter() - tc)

    def drain():
        while sent:
            report(settle())

    wall_start, w0 = time.time(), time.perf_counter()
    train.report({"window_start_wall": wall_start, "setup_timing": timing})
    n_compiles0 = len(compiles)
    while True:
        now = time.perf_counter() - w0
        if now >= seconds:
            break
        if opts.get("trace") and traced is None:
            # a traced window holds whole steps (planes/train.py)
            if not tracing and now >= trace_at:
                drain()
                step_s = statistics.median(spans["step"] or [0.0])
                jax.profiler.start_trace(trace_dir)
                tracing, traced_from = True, time.perf_counter() - w0
            elif tracing and (now - traced_from + len(sent) * step_s
                              >= trace_s):
                drain()
                jax.profiler.stop_trace()
                tracing, traced = False, trace_dir
        ta = time.perf_counter()
        with jax.profiler.TraceAnnotation("data"):
            tokens = jnp.asarray(host_batch())
        tb = time.perf_counter()
        spans["data"].append(tb - ta)
        with jax.profiler.TraceAnnotation("step"):
            state, opt_state, met = step(state, opt_state, tokens)
            sent.append((met, tb))
            met = settle() if len(sent) > ahead else None
        if met is not None:
            report(met)
    drain()
    elapsed = time.perf_counter() - w0
    if tracing:
        jax.profiler.stop_trace()
        traced = trace_dir
    steps = len(spans["step"])

    out = {"final": True, "device": device, "timing": timing,
           "check": check, "losses": losses, "steps": steps,
           "elapsed_s": elapsed, "tokens_per_step": batch * seq,
           "spans": spans, "ends_s": [t - w0 for t in ends], "ahead": ahead,
           "window_start_wall": wall_start,
           "compiles_in_window": compiles[n_compiles0:],
           "step_metrics": {k: [h[k] for h in history[2:]]
                            for k in STEP_METRICS},
           "traced_steps": [i - 2 for i in traced_steps]}
    ms = _common.memory_stats()
    out["memory_runtime_peak_bytes"] = int(ms.get("peak_bytes_in_use", 0))
    out["memory_program_bytes"] = program_bytes
    out["memory_peak_bytes"] = max(out["memory_runtime_peak_bytes"],
                                   program_bytes)
    out["memory_limit_bytes"] = int(ms.get("bytes_limit", 0))
    if traced:
        from benchmarks import trace_reduce, trace_scopes

        out["trace"] = trace_reduce.reduce_dir(
            traced, annotations=ANNOTATIONS, default_gap="train loop, other",
            kernels=kernel_predicates(cfg, mix))
        if out["trace"].get("devices"):
            out["trace"]["scopes"] = trace_scopes.scope_seconds(
                traced, [Scope(s) for s in SCOPES], PROGRAM_PREFIXES)
        keep = os.environ.get("RT_BENCH_KEEP_TRACE")
        if keep:
            trace_reduce.copy_xplane(traced, keep)
    train.report(out)


def run(cell: dict, cfg: dict, mix: dict, args, t_process_start: float) -> dict:
    # a checkout without the model (the parent of the PR that added it)
    # fails HERE, at once, before any runtime is started
    missing = [p for p in (os.path.join(REPO, "ray_tpu", *q) for q in NEED)
               if not os.path.exists(p)]
    if missing:
        raise RuntimeError(
            f"this checkout cannot run {cell['name']}: it has no "
            f"{', '.join(os.path.relpath(p, REPO) for p in missing)}")
    if args.control and args.control not in CONTROLS:
        raise ValueError(f"no control {args.control!r}: {CONTROLS}")
    import ray_tpu as rt
    from ray_tpu import train

    if mix["kind"] != "train_stream":
        raise ValueError(f"the train plane cannot run {mix['kind']!r}")
    rt.init(num_workers=2, num_cpus=4)
    scaling = (train.ScalingConfig(num_workers=1) if args.rehearse else
               train.ScalingConfig(num_workers=1, use_tpu=True))
    trainer = train.JaxTrainer(
        train_loop,
        train_loop_config={
            "cfg": cfg, "mix": mix, "seed": args.seed,
            "seconds": args.seconds,
            "opts": {"bench_dir": os.environ["RT_BENCH_DIR"],
                     "rehearse": bool(args.rehearse), "trace": bool(args.trace),
                     "control": args.control}},
        scaling_config=scaling,
        run_config=train.RunConfig(name="bench",
                                   storage_path=os.environ["RT_TMPDIR"]))
    result = trainer.fit()
    if result.error is not None:
        raise result.error
    final = next(x for x in result.metrics_history if x.get("final"))
    reported = [x for x in result.metrics_history if "step" in x]
    return {
        "plane": "train", "seconds": float(args.seconds),
        "setup_s": final["window_start_wall"] - t_process_start,
        "train": final, "reported_steps": len(reported),
        # every report carries the counters beside the loss
        "reported_with_counters": sum(
            all(k in x for k in STEP_METRICS) for x in reported),
    }


def verdict(ctx: dict, cfg: dict) -> dict:
    lim, t = cfg["reference"], ctx["train"]
    losses = t["losses"]
    ln_v = math.log(cfg["model"]["vocab_size"])
    finite = all(math.isfinite(x) for x in losses)
    m = cfg["model"]
    # what a balanced router sends this chip's experts, a token
    want = (m["num_experts_per_tok"] * m["num_experts"]
            / cfg["deployment"]["router_experts"]
            * (len(m["layer_types"]) - m["num_dense_layers"]))
    held = [x / t["tokens_per_step"] for x in t["step_metrics"]["held_pairs"]]
    off = lambda xs: (abs(statistics.fmean(xs) / want - 1)  # noqa: E731
                      if xs else math.inf)
    rows = [
        ("grad_rel_err_vs_reference", t["check"]["grad_rel_err"],
         lim["grad_rel_err_limit"]),
        ("held_experts_grad_rel_err_vs_reference",
         t["check"]["held_grad_rel_err"], lim["held_grad_rel_err_limit"]),
        ("sample_held_pairs_off_balance_share",
         off([t["check"]["sample_held_pairs_per_token"]]),
         lim["sample_held_pairs_band"]),
        # a routing that collapsed skips the expert work: faster, wrong
        ("window_held_pairs_off_balance_share", off(held),
         lim["held_pairs_band"]),
        ("last_steps_held_pairs_off_balance_share",
         off(held[-int(lim["held_pairs_last_steps"]):]),
         lim["held_pairs_band"]),
        ("loss_abs_diff_vs_reference", t["check"]["loss_abs_diff"],
         lim["loss_abs_diff_limit"]),
        ("bias_entries_off_the_rule_share", t["check"]["bias_off_share"],
         lim["bias_off_share_limit"]),
        ("first_loss_minus_ln_vocab_abs", abs(losses[0] - ln_v)
         if finite else math.inf, lim["first_loss_band"]),
        ("last_loss_minus_first", (losses[-1] - losses[0])
         if finite else math.inf, -lim["min_loss_drop"]),
        ("steps_reported_missing", t["steps"] - ctx["reported_steps"], 0),
        ("reports_without_counters",
         ctx["reported_steps"] - ctx["reported_with_counters"], 0),
    ]
    return {"rows": rows, "correct": all(v <= l for _, v, l in rows)}
