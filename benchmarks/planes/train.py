"""The train plane as a user runs it: `JaxTrainer` with one worker
holding the chip, a fresh host batch every step, `train.report` every
step.  The loop is the benchmark's own: it makes the weights from the
seed, holds the system's loss and gradients to the plain reference on
a seeded sample before anything is timed, annotates its three phases
(`data`, `step`, `report`) for the trace, and takes the trace itself.

The loop keeps the chip fed while the host stands still: it dispatches
`ahead_steps` steps (the mix's; some five seconds of work) beyond the
one whose loss it waits for, so a step's loss is read, and reported,
that many steps late.  When the window's time is up it sends nothing
more, waits for every step it sent, and reads the clock after that
wait: every step counts, over all of that time.
"""

from __future__ import annotations

import collections
import math
import os
import statistics
import time

ANNOTATIONS = ("data", "step", "report")


def kernel_predicates(cfg: dict, mix: dict) -> dict:
    """How the trace prints the flash-attention kernels.  It has no
    kernel names (they are `tpu_custom_call`s named after whatever
    wraps them: `closed_call.N`, `rematted_computation.N`,
    `checkpoint.N`), so they are told apart by what they return: the
    forward gives the output and the row log-sum-exp, `(bf16[B*H,T,hd],
    f32[B*H,T,1])`; the fused backward gives dQ, dK and dV."""
    m = cfg["model"]
    bh, t = int(mix["batch"]) * m["n_head"], int(mix["seq"])
    o = f"bf16[{bh},{t},{m['n_embd'] // m['n_head']}]"
    lse = f"f32[{bh},{t},1]"

    def result(n):
        if "custom-call(" not in n or "tpu_custom_call" not in n:
            return ""
        return n.split("=", 1)[1].split(" custom-call(")[0]

    return {
        "flash_fwd": lambda n: result(n).count(o) == 1 and lse in result(n),
        "flash_bwd": lambda n: result(n).count(o) == 3,
    }


def zipf_p(vocab: int, a: float):
    import numpy as np

    p = 1.0 / np.arange(1, vocab + 1) ** a
    return p / p.sum()


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

    from benchmarks import weights
    from benchmarks.planes import _common
    from benchmarks.reference import gpt2 as ref
    from benchmarks.reference import precision
    from ray_tpu import train
    from ray_tpu.core.accelerators import device_report
    from ray_tpu.models import gpt2

    compiles = _common.count_compiles()
    device = device_report()
    timing = {"jax_start_s": time.perf_counter() - t0}
    m, tr = cfg["model"], cfg["trainer"]
    gcfg = gpt2.GPT2Config(
        vocab_size=m["vocab_size"], n_positions=m["n_positions"],
        n_embd=m["n_embd"], n_layer=m["n_layer"], n_head=m["n_head"],
        attention=tr["attention"], remat=tr.get("remat", True),
        logits_dtype=jnp.bfloat16 if tr["logits_dtype"] == "bfloat16"
        else jnp.float32)
    params = weights.gpt2_params(m, seed)
    jax.block_until_ready(params)
    timing["weights_s"] = time.perf_counter() - t0

    # -- correct, part 1: loss and gradient on a seeded sample ---------
    batch, seq = int(mix["batch"]), int(mix["seq"])
    p = zipf_p(m["vocab_size"], float(mix["zipf_a"]))
    rng = np.random.default_rng([seed, 0x7A1])
    sample = jnp.asarray(rng.choice(
        m["vocab_size"], size=(int(cfg["reference"]["sample"]), seq + 1),
        p=p).astype(np.int32))
    ref_fn = jax.jit(lambda pr, t: ref.loss_and_grad(pr, t, m["n_head"]))
    if opts.get("control"):
        hook = precision.HOOKS[opts["control"]]
        sys_fn = jax.jit(
            lambda pr, t: ref.loss_and_grad(pr, t, m["n_head"], hook))
    else:
        sys_fn = jax.jit(jax.value_and_grad(
            lambda pr, t: gpt2.loss_fn(gcfg, pr, t)))

    @jax.jit
    def compare(gs, gr):
        sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))  # noqa: E731
                           for x in jax.tree.leaves(t))
        diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, gs, gr)
        return jnp.sqrt(sq(diff) / sq(gr)), jnp.sqrt(sq(gs)), jnp.sqrt(sq(gr))

    l_ref, g_ref = ref_fn(params, sample)
    l_sys, g_sys = sys_fn(params, sample)
    rel, n_sys, n_ref = compare(g_sys, g_ref)
    check = {"loss_ref": float(l_ref), "loss_sys": float(l_sys),
             "loss_abs_diff": abs(float(l_sys) - float(l_ref)),
             "grad_rel_err": float(rel), "grad_norm_sys": float(n_sys),
             "grad_norm_ref": float(n_ref)}
    del g_ref, g_sys, ref_fn, sys_fn
    timing["check_s"] = time.perf_counter() - t0

    # -- the step, as chip_smoke and the examples build it -------------
    opt = optax.chain(
        optax.clip_by_global_norm(tr["clip_norm"]),
        optax.adamw(tr["lr"], b1=tr["b1"], b2=tr["b2"],
                    weight_decay=tr["weight_decay"]))
    step = jax.jit(gpt2.make_train_step(gcfg, opt), donate_argnums=(0, 1))
    opt_state = opt.init(params)
    data_rng = np.random.default_rng([seed, 0xDA7A])

    def host_batch():
        return data_rng.choice(m["vocab_size"], size=(batch, seq + 1),
                               p=p).astype(np.int32)

    # compiled once, ahead of the first call, so that the program's own
    # account of its memory can be read: the runtime's peak counter
    # leaves out a program's temporaries (measured: it reads 4.3 GB at
    # any batch size, the program needs 11 GB)
    step = step.lower(params, opt_state, jnp.asarray(host_batch())).compile()
    ma = step.memory_analysis()
    program_bytes = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                        + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    losses = []
    for _ in range(2):  # two warm steps
        params, opt_state, met = step(params, opt_state,
                                      jnp.asarray(host_batch()))
        losses.append(float(met["loss"]))
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

    def settle():
        """Wait for the oldest step in flight.  Its `step` span is the
        time from the step's dispatch, or from the end of the step
        before it where that is later, to the read of its loss: what
        the step took on the chip while the chip is kept fed."""
        met, at = sent.popleft()
        loss = float(met["loss"])  # device -> host: the step has ended
        t = time.perf_counter()
        spans["step"].append(t - max(at, ends[-1] if ends else at))
        ends.append(t)
        return loss

    def report(loss):
        tc = time.perf_counter()
        with jax.profiler.TraceAnnotation("report"):
            train.report({"step": len(losses), "loss": loss})
        losses.append(loss)
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
            # a traced window holds whole steps: the steps in flight
            # are waited for before the trace starts and before it
            # stops (a traced run's own cost, as the profiler's is),
            # and it stops sending once the steps in flight carry the
            # trace to `trace_s`
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
            params, opt_state, met = step(params, opt_state, tokens)
            sent.append((met, tb))
            loss = settle() if len(sent) > ahead else None
        if loss is not None:
            report(loss)
    # the window's time is up: nothing more is sent, every step that
    # was sent is waited for, and the clock is read after that wait
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
           "compiles_in_window": compiles[n_compiles0:]}
    ms = _common.memory_stats()
    out["memory_runtime_peak_bytes"] = int(ms.get("peak_bytes_in_use", 0))
    out["memory_program_bytes"] = program_bytes
    out["memory_peak_bytes"] = max(out["memory_runtime_peak_bytes"],
                                   program_bytes)
    out["memory_limit_bytes"] = int(ms.get("bytes_limit", 0))
    if traced:
        from benchmarks import trace_reduce

        out["trace"] = trace_reduce.reduce_dir(
            traced, annotations=ANNOTATIONS, default_gap="train loop, other",
            kernels=kernel_predicates(cfg, mix))
        keep = os.environ.get("RT_BENCH_KEEP_TRACE")
        if keep:
            trace_reduce.copy_xplane(traced, keep)
    train.report(out)


def run(cell: dict, cfg: dict, mix: dict, args, t_process_start: float) -> dict:
    import ray_tpu as rt
    from ray_tpu import train

    if mix["kind"] != "train_stream":
        raise ValueError(f"the train plane cannot run {mix['kind']!r}")
    rt.init(num_workers=2, num_cpus=4)
    scaling = (train.ScalingConfig(num_workers=1) if args.rehearse else
               train.ScalingConfig(num_workers=1, use_tpu=True) if
               int(cell["chips"]) == 1 else
               train.ScalingConfig(
                   num_workers=1, use_tpu=True,
                   resources_per_worker={"CPU": 1.0,
                                         "TPU": float(cell["chips"])}))
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
    }


def verdict(ctx: dict, cfg: dict) -> dict:
    lim, t = cfg["reference"], ctx["train"]
    losses = t["losses"]
    ln_v = math.log(cfg["model"]["vocab_size"])
    finite = all(math.isfinite(x) for x in losses)
    rows = [
        ("grad_rel_err_vs_reference", t["check"]["grad_rel_err"],
         lim["grad_rel_err_limit"]),
        ("loss_abs_diff_vs_reference", t["check"]["loss_abs_diff"],
         lim["loss_abs_diff_limit"]),
        ("first_loss_minus_ln_vocab_abs", abs(losses[0] - ln_v)
         if finite else math.inf, lim["first_loss_band"]),
        ("last_loss_minus_first", (losses[-1] - losses[0])
         if finite else math.inf, -lim["min_loss_drop"]),
        ("steps_reported_missing", t["steps"] - ctx["reported_steps"], 0),
    ]
    return {"rows": rows, "correct": all(v <= l for _, v, l in rows)}
