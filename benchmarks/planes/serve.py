"""The serve plane as a user runs it: `serve.run` -> HTTP proxy -> router
-> replica -> `LlamaEngine`, one replica per chip.  The deployment is
the benchmark's own: it builds the model from the configuration file
with seeded weights, warms every shape the cell's traffic can produce,
times `engine.submit` from the inside, takes the device trace (only
the process that holds the chip can), and after the window holds its
own answers to the plain reference.

The client side (`run`) never imports JAX.  It talks to the replicas
over HTTP for requests and through small files under `$RT_BENCH_DIR`
for everything else, because a handle call reaches one replica of the
router's choosing and these have to reach all of them.
"""

from __future__ import annotations

import glob
import json
import math
import os
import threading
import time

from benchmarks import loadgen
from benchmarks.planes import _common

APP, ROUTE = "bench", "/bench"
# the engine loop's spans in a trace (`LlamaEngine._span`): the blocked
# wait between ticks, a tick, and its phases, some inside others; an
# idle gap of the device is named after the innermost
ENGINE_SPANS = tuple("engine." + n for n in (
    "wait", "tick", "admit", "plan", "prefill", "dispatch", "harvest",
    "device_wait", "harvest_host"))


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ----------------------------------------------------------------------
# which shapes a mix can produce (the engine's bucketing, restated)
# ----------------------------------------------------------------------
def gather_widths(plen: int, olen: int, eng: dict) -> list:
    """Block-table widths one request alone walks through: the pow-2
    bucket of the blocks its next chunk can touch, capped at the blocks
    it was given and at a maximal sequence's."""
    bs, chunk, max_len = eng["block_size"], eng["chunk"], eng["max_len"]
    alloc = _cdiv(plen + olen - 1, bs)
    out = []
    for pos in range(plen, plen + olen + chunk, chunk):
        hi = min(pos + chunk - 1, max_len - 1)
        w = min(hi // bs + 1, alloc)
        W = min(_next_pow2(w), _cdiv(max_len, bs))
        if W not in out:
            out.append(W)
    return out


def warmup_plan(mix: dict, eng: dict) -> dict:
    """`alone`: (prompt, output) pairs sent one at a time, the cheapest
    that reaches each gather width; `together`: one request per prompt
    length, covering every prefill bucket and block-write shape."""
    plens = loadgen.possible_lengths(mix["prompt_len"])
    olens = loadgen.possible_lengths(mix["output_len"])
    step = int(mix.get("first_output_step", 0))
    if step:
        olens = sorted(set(olens) | set(range(step, max(olens) + 1, step)))
    best = {}
    for p in plens:
        for o in olens:
            for i, W in enumerate(gather_widths(p, o, eng)):
                cost = (i + 1) * eng["chunk"]  # tokens until W shows up
                if W not in best or cost < best[W][0]:
                    best[W] = (cost, p, min(o, _cdiv(cost, eng["chunk"])
                                            * eng["chunk"]))
    return {
        "alone": [(p, max(o, 2)) for _, p, o in
                  (best[W] for W in sorted(best))],
        "together": [(p, min(olens)) for p in plens],
        "widths": sorted(best),
    }


def kernel_predicates(cfg: dict) -> dict:
    """How the trace prints the engine's Pallas kernels.  The trace has
    no kernel names (both are `tpu_custom_call`s called `closed_call.N`),
    so they are told apart by what they return: decode attention gives
    `[slots, heads, head_dim]`; the KV append gives the two pools back
    (aliased to its operands)."""
    m, e = cfg["model"], cfg["engine"]
    out_shape = (f"bf16[{e['slots']},{m['num_attention_heads']},"
                 f"{m['head_dim']}]")

    def is_kernel(n):
        return "custom-call(" in n and "tpu_custom_call" in n

    return {
        "paged_decode": lambda n: is_kernel(n) and n.split("=", 1)[1]
        .lstrip().startswith(out_shape),
        "paged_append": lambda n: is_kernel(n)
        and "output_to_operand_aliasing" in n,
    }


# ----------------------------------------------------------------------
# the deployment (runs in the replica: the chip's owner)
# ----------------------------------------------------------------------
class BenchLlamaService:
    def __init__(self, cfg: dict, mix: dict, seed: int, opts: dict):
        t0 = time.perf_counter()
        self.cfg, self.seed = cfg, int(seed)
        self.rid = str(os.getpid())
        self.dir = opts["bench_dir"]
        if opts.get("rehearse"):
            import jax

            jax.config.update("jax_platforms", "cpu")
        import jax
        import jax.numpy as jnp

        from benchmarks import weights
        from ray_tpu.core.accelerators import device_report
        from ray_tpu.models import llama
        from ray_tpu.serve.llm_engine import LlamaEngine

        self._jax = jax
        self._compiles = _common.count_compiles()
        self.device = device_report()  # first touch of JAX
        timing = {"jax_start_s": time.perf_counter() - t0}
        m, e = cfg["model"], cfg["engine"]
        self.lcfg = llama.LlamaConfig(
            vocab_size=m["vocab_size"], max_seq_len=m["max_position_embeddings"],
            dim=m["hidden_size"], n_layers=m["num_hidden_layers"],
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"],
            intermediate=m["intermediate_size"], rope_theta=m["rope_theta"],
            norm_eps=m["rms_norm_eps"], dtype=jnp.bfloat16)
        assert self.lcfg.head_dim == m["head_dim"]
        params = weights.llama_params(m, self.seed)
        if opts.get("control") == "int8":
            # the program's own lower-precision path, switched on
            params = jax.jit(llama.quantize_weights_int8,
                             donate_argnums=0)(params)
        jax.block_until_ready(params)
        timing["weights_s"] = time.perf_counter() - t0
        self.engine = LlamaEngine(
            self.lcfg, params, slots=e["slots"], max_len=e["max_len"],
            chunk=e["chunk"], block_size=e["block_size"],
            kv_blocks=e["kv_blocks"], prefix_cache=e["prefix_cache"],
            decode_kernel=e["decode_kernel"])
        timing["engine_s"] = time.perf_counter() - t0
        self.plan = warmup_plan(mix, e)
        self._warm()
        timing["warm_s"] = time.perf_counter() - t0
        timing["compiles_in_setup"] = len(self._compiles)
        self._served = []      # (prompt, output) of every answer
        self._window = None    # (wall start, seconds)
        self._ttft_polls = []
        self._trace = None
        self._seen = set()
        self._stop = False
        threading.Thread(target=self._side_channel, name="bench-side",
                         daemon=True).start()
        self._write(f"ready_{self.rid}.json", {
            "rid": self.rid, "device": self.device, "timing": timing,
            "plan": self.plan, "wall_ready": time.time()})

    # -- warm-up: every shape the mix can produce ----------------------
    def _warm(self):
        import numpy as np

        rng = np.random.default_rng([self.seed, 0xA11])
        V = self.cfg["model"]["vocab_size"]

        def go(p, o):
            return self.engine.submit(rng.integers(1, V, size=p).tolist(), o)

        for p, o in self.plan["alone"]:
            go(p, o).result(timeout=900)
        for f in [go(p, o) for p, o in self.plan["together"]]:
            f.result(timeout=900)

    # -- requests ------------------------------------------------------
    async def __call__(self, request):
        import asyncio

        body = request.json()
        prompt = body["tokens"][0]
        t0 = time.perf_counter()
        out = await asyncio.wrap_future(
            self.engine.submit(list(prompt), int(body["max_new_tokens"])))
        dt = time.perf_counter() - t0
        self._served.append((prompt, out))
        return {"tokens": [out], "engine_s": dt, "replica": self.rid}

    def stats(self):
        """Load signals for the router; the tick ring stays here (it is
        read once, at the end, not shipped with every health check)."""
        s = self.engine.stats()
        s.pop("tick_ring", None)
        return s

    # -- side channel: commands that must reach every replica ----------
    def _write(self, name: str, obj) -> None:
        tmp = os.path.join(self.dir, f".{name}.{self.rid}.tmp")
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, os.path.join(self.dir, name))

    def _side_channel(self):
        while not self._stop:
            for path in sorted(glob.glob(os.path.join(self.dir, "cmd_*.json"))):
                if path in self._seen:
                    continue
                self._seen.add(path)
                try:
                    with open(path) as f:
                        cmd = json.load(f)
                    getattr(self, "_cmd_" + cmd["op"])(cmd)
                except Exception as e:  # report, never die silently
                    import traceback

                    self._write(f"error_{self.rid}.json", {
                        "cmd": path, "error": repr(e),
                        "traceback": traceback.format_exc()})
            if self._window is not None:
                now = time.time()
                w0, secs = self._window
                if w0 <= now <= w0 + secs and (
                        not self._ttft_polls
                        or now - self._ttft_polls[-1][0] >= 1.0):
                    self._ttft_polls.append(
                        (now, self.engine.stats().get("ttft_p90_s", 0.0)))
            time.sleep(0.05)

    def _cmd_window(self, cmd):
        self._window = (cmd["wall_start"], cmd["seconds"])

    def _cmd_trace(self, cmd):
        def run():
            jax = self._jax
            time.sleep(max(0.0, cmd["wall_start"] - time.time()))
            d = os.path.join(self.dir, f"trace_{self.rid}")
            t0 = time.time()
            jax.profiler.start_trace(d)
            time.sleep(cmd["seconds"])
            jax.profiler.stop_trace()
            self._trace = {"dir": d, "wall_start": t0,
                           "wall_stop": time.time()}

        self._trace_thread = threading.Thread(target=run, daemon=True)
        self._trace_thread.start()

    def _cmd_finish(self, cmd):
        """After the window, engine idle: counters, trace, reference."""
        out = {"rid": self.rid, "device": dict(self.device),
               "served": len(self._served)}
        t = getattr(self, "_trace_thread", None)
        if t is not None:
            t.join(timeout=120)
        stats = self.engine.stats()
        out["engine"] = {k: v for k, v in stats.items() if k != "tick_ring"}
        out["tick_ring"] = [
            {k: r[k] for k in ("seq", "t_wall", "admitted", "active",
                               "queued", "live_tokens", "gather_blocks",
                               "admit_s", "dispatch_s", "harvest_s")
             if k in r}
            for r in stats.get("tick_ring", [])]
        w0, _ = self._window or (0.0, 0.0)
        out["compiles_in_window"] = [
            c for c in self._compiles if w0 <= c[0] <= cmd["wall_end"]]
        out["ttft_p90_polls_s"] = [v for _, v in self._ttft_polls]
        ms = _common.memory_stats()
        out["memory_peak_bytes"] = int(ms.get("peak_bytes_in_use", 0))
        out["memory_limit_bytes"] = int(ms.get("bytes_limit", 0))
        if self._trace is not None:
            from benchmarks import trace_reduce

            out["trace"] = trace_reduce.reduce_dir(
                self._trace["dir"], annotations=ENGINE_SPANS,
                default_gap="engine loop, unattributed",
                kernels=kernel_predicates(self.cfg))
            keep = cmd.get("keep_trace_to")
            if keep:
                trace_reduce.copy_xplane(self._trace["dir"], keep)
        out["check"] = self._reference_check(int(cmd.get("sample", 8)))
        self._write(f"result_{self.rid}.json", out)

    # -- `correct`: the served tokens against the plain reference -------
    def _reference_check(self, sample: int) -> dict:
        """Teacher-forces a seeded sample of this replica's own answers
        through the float32 reference, one layer at a time, each
        layer's weights made again from the seed, and reports how far
        each served token sits below the reference's largest logit."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks import weights
        from benchmarks.reference import mistral as ref

        m = self.cfg["model"]
        served = list(self._served)
        if not served:
            return {"sampled": 0, "tokens": 0}
        rng = np.random.default_rng([self.seed, 0xC0DE])
        pick = rng.permutation(len(served))[:sample]
        span = int(self.cfg["reference"]["positions"])  # last <= span answers
        longest = max(len(served[i][0]) + len(served[i][1]) for i in pick)
        T = max(_cdiv(longest, 128) * 128, span)
        toks = np.zeros((len(pick), T), np.int32)
        answers = np.zeros((len(pick), span), np.int32)
        starts, offs, counts = [], [], []
        for r, i in enumerate(pick):
            p, full_g = served[i]
            g = full_g[-span:]
            full = list(p) + list(full_g)
            toks[r, :len(full) - 1] = full[:-1]
            start = len(full) - len(g) - 1   # position that predicts g[0]
            s0 = min(start, T - span)        # the slice has to fit
            starts.append(s0)
            offs.append(start - s0)
            counts.append(len(g))
            answers[r, start - s0:start - s0 + len(g)] = g
        kw = dict(n_heads=m["num_attention_heads"],
                  n_kv_heads=m["num_key_value_heads"],
                  head_dim=m["head_dim"], rope_theta=m["rope_theta"],
                  eps=m["rms_norm_eps"])

        @jax.jit
        def one_layer(x, w):
            return jax.lax.map(lambda xb: ref.layer(xb, w, **kw), x)

        @jax.jit
        def tail(x, start, answer, ends):
            def one(args):
                xb, s, a = args
                lg = ref.head(jax.lax.dynamic_slice_in_dim(xb, s, span, 0),
                              ends["final_norm"], ends["lm_head"],
                              m["rms_norm_eps"])
                return ref.margins(lg, a), jnp.std(lg)
            return jax.lax.map(one, (x, start, answer))

        t0 = time.perf_counter()
        ends = weights.llama_ends(m, self.seed)
        x = jax.jit(lambda t, e: jax.vmap(
            lambda tt: ref.embed(tt, e))(t))(jnp.asarray(toks), ends["tok_emb"])
        for l in range(m["num_hidden_layers"]):
            x = one_layer(x, weights.llama_layer(m, self.seed, l))
        marg, std = tail(x, jnp.asarray(starts, jnp.int32),
                         jnp.asarray(answers), ends)
        marg = np.asarray(marg)
        vals = np.concatenate([marg[r, o:o + c]
                               for r, (o, c) in enumerate(zip(offs, counts))])
        return {
            "sampled": int(len(pick)), "tokens": int(vals.size),
            "max_margin": float(vals.max()),
            "mean_margin": float(vals.mean()),
            "flipped_share": float((vals > 0).mean()),
            "logit_std": float(np.asarray(std).mean()),
            "seconds": time.perf_counter() - t0,
        }


# ----------------------------------------------------------------------
# the client side (runs in the cell's process; no JAX here)
# ----------------------------------------------------------------------
def _wait_files(pattern: str, n: int, timeout: float, bench_dir: str) -> list:
    deadline = time.time() + timeout
    while time.time() < deadline:
        errs = glob.glob(os.path.join(bench_dir, "error_*.json"))
        if errs:
            with open(errs[0]) as f:
                raise RuntimeError(f"a replica failed: {f.read()}")
        got = sorted(glob.glob(os.path.join(bench_dir, pattern)))
        if len(got) >= n:
            out = []
            for p in got:
                with open(p) as f:
                    out.append(json.load(f))
            return out
        time.sleep(0.1)
    raise TimeoutError(f"{pattern}: {n} file(s) expected under {bench_dir}")


def _command(bench_dir: str, n: int, **cmd) -> None:
    tmp = os.path.join(bench_dir, f".cmd_{n}.tmp")
    with open(tmp, "w") as f:
        json.dump(cmd, f)
    os.replace(tmp, os.path.join(bench_dir, f"cmd_{n:03d}_{cmd['op']}.json"))


def run(cell: dict, cfg: dict, mix: dict, args, t_process_start: float) -> dict:
    """Deploys, offers the mix for `args.seconds`, gathers every
    replica's counters.  Returns the context the metric readers get."""
    import ray_tpu as rt
    from ray_tpu import serve

    bench_dir = os.environ["RT_BENCH_DIR"]
    replicas = int(cell["chips"])
    vocab = cfg["model"]["vocab_size"]
    seconds = float(args.seconds)
    drain_s = float(mix.get("drain_s", 30.0))
    # the whole window's requests, before anything is timed
    if mix["kind"] == "open_loop":
        plan = loadgen.open_loop_schedule(mix, seconds, args.seed, vocab)
    elif mix["kind"] == "closed_loop":
        plan = loadgen.closed_loop_schedule(mix, args.seed, vocab)
    else:
        raise ValueError(f"the serve plane cannot offer {mix['kind']!r}")

    rt.init(num_workers=replicas + 3, num_cpus=2 * replicas + 6)
    opts = {"bench_dir": bench_dir, "rehearse": bool(args.rehearse),
            "control": args.control}
    dep = serve.deployment(
        name="BenchLlamaService", num_replicas=replicas,
        max_ongoing_requests=4096, health_check_timeout_s=120.0,
        ray_actor_options=None if args.rehearse else {"num_tpus": 1},
    )(BenchLlamaService)
    serve.run(dep.bind(cfg, mix, args.seed, opts), name=APP,
              route_prefix=ROUTE, timeout_s=1100.0)
    ready = _wait_files("ready_*.json", replicas, 60.0, bench_dir)
    host, port = serve.http_address()
    url = f"http://{host}:{port}{ROUTE}"
    # the HTTP path once per replica's worth, on a shape already warm
    p0, o0 = ready[0]["plan"]["together"][0]
    import numpy as np

    warm = [loadgen.Request(-1 - i, 0.0, np.random.default_rng(
        [args.seed, 0xB00, i]).integers(1, vocab, size=p0).tolist(), o0)
        for i in range(2 * replicas)]
    recs = loadgen.run_open_loop(url, warm, 0.0, 120.0)
    bad = [r.error for r in recs if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up over HTTP failed: {bad[:3]}")

    for rate in getattr(args, "sweep", None) or []:
        # builder's tool: one deployment, several offered rates; the
        # knee is read off these lines once, when the cell is defined
        sw = loadgen.open_loop_schedule({**mix, "rate_per_s": rate}, seconds,
                                        args.seed + int(rate * 1000), vocab)
        t0 = time.time()
        rr = loadgen.run_open_loop(url, sw, seconds, drain_s)
        sm = loadgen.summarize(rr, seconds, (seconds + drain_s) * 1e3)
        print(json.dumps({
            "note": "sweep", "rate_per_s": rate, "attempted": sm["attempted"],
            "failed": sm["failed"],
            "completed_in_window": sm["completed_in_window"],
            "unanswered_at_window_end": sm["unanswered_at_window_end"],
            "tokens_per_s": sm["tokens_per_s"],
            "p50_ms": loadgen.percentile(sm["latency_ms"], 50),
            "p95_ms": loadgen.percentile(sm["latency_ms"], 95),
            "late_p95_ms": loadgen.percentile(sm["late_ms"], 95),
            "drained_after_s": time.time() - t0 - seconds}), flush=True)

    wall_start = time.time()
    setup_s = wall_start - t_process_start
    _command(bench_dir, 1, op="window", wall_start=wall_start,
             seconds=seconds)
    if args.trace:
        _command(bench_dir, 2, op="trace",
                 wall_start=wall_start + float(mix.get("trace_at_s", 0.4 * seconds)),
                 seconds=float(mix.get("trace_s", 3.0)))
    if mix["kind"] == "open_loop":
        recs = loadgen.run_open_loop(url, plan, seconds, drain_s)
    else:
        recs = loadgen.run_closed_loop(url, plan, seconds, drain_s)
    window_end = time.time()
    _command(bench_dir, 3, op="finish", wall_end=window_end,
             sample=int(cfg["reference"]["sample"]),
             keep_trace_to=os.environ.get("RT_BENCH_KEEP_TRACE"))
    results = _wait_files("result_*.json", replicas, 300.0, bench_dir)
    ring_to(results, wall_start + seconds)
    summary = loadgen.summarize(recs, seconds, (seconds + drain_s) * 1e3,
                                closed=mix["kind"] == "closed_loop")
    return {
        "plane": "serve", "setup_s": setup_s, "seconds": seconds,
        "client": summary, "replicas": results, "ready": ready,
        "check_s": time.time() - window_end,
        # the client's own records (`--detail` keeps them): what both
        # readings of a closed loop's rate are reduced from
        "records": [[r.sent_s, r.done_s, r.got, r.ok] for r in recs],
    }


def ring_to(results: list, wall_end: float) -> None:
    """The ring the readers average ends where the window does: the
    ticks after it are of an engine that admits nothing and empties
    (3-12 s of a closed loop, whose requests out at the window's end
    are waited for).  A tick without a stamp stays."""
    for r in results:
        r["tick_ring"] = [t for t in r["tick_ring"]
                          if t.get("t_wall", 0.0) < wall_end]


def verdict(ctx: dict, cfg: dict) -> dict:
    """`correct`: each number compared, beside its limit."""
    lim = cfg["reference"]
    checks = [r["check"] for r in ctx["replicas"] if r["check"]["sampled"]]
    tokens = sum(c["tokens"] for c in checks)
    mean = (sum(c["mean_margin"] * c["tokens"] for c in checks) / tokens
            if tokens else math.inf)
    worst = max((c["max_margin"] for c in checks), default=math.inf)
    rows = [
        ("mean_margin_below_reference_argmax", mean, lim["mean_margin_limit"]),
        ("max_margin_below_reference_argmax", worst, lim["max_margin_limit"]),
        ("sampled_tokens_at_least", -tokens, -lim["min_tokens"]),
    ]
    if (ctx.get("traffic") or {}).get("kind") == "closed_loop":
        # a closed cell's reading credits every request sent in the
        # window, so each has to come back before the drain's ceiling
        rows.append(("cut_at_end", ctx["client"]["cut_at_end"], 0))
    return {"rows": rows, "correct": all(v <= l for _, v, l in rows)}
