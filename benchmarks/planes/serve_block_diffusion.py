"""The serve plane for a decoder that generates by DIFFUSION OVER BLOCKS
(`ray_tpu.models.sdar`: the Qwen3-MoE block, every layer a softmax-routed
expert layer, a block of `B` positions filled over a few denoising
forwards and committed by one more): the same path a user's request
takes as in `planes/serve.py` (`serve.run` -> HTTP proxy -> router ->
replica -> `LlamaEngine`), the same client, warm-up plan, side channel
and window, imported from there.  What differs is what the replica
builds, what a request carries, and what is checked:

- the model's config and seeded weights (`weights_sdar.py`);
- a request's body carries `denoising_steps` (the traffic file's
  `request_fields`), which the replica hands to `submit`; an answer
  comes back with the step each token was decided at;
- after the window it holds a sample of its own answers, over every
  prompt length and every step count of the mix, WHOLE sequences, to the
  plain float32 reference's teacher-forced `replay`
  (`reference/sdar.py`), the engine's memory given back first: the
  TOKEN margin at every position at the step it was decided, and the
  CHOICE margin wherever a step had to choose among undecided positions;
- the two controls; the shape the block step's attention kernel prints
  in a trace (named `paged_decode`, so that `decode_step_ms` reads one
  FORWARD); the scopes' device time in the decode programs; the tick
  ring's counters of row-forwards by kind.

The context says `"plane": "serve"`: `cell.py` treats it as the serve
plane it is.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks.manifest import REPO
from benchmarks.planes import _common
from benchmarks.planes import serve as base

# the parts of a forward the program marks with `jax.named_scope`
SCOPES = ("block_attn", "block_kv_write", "moe_router", "moe_routed",
          "lm_head", "unmask")
TICK_KEYS = ("seq", "t_wall", "admitted", "active", "queued", "live_tokens",
             "gather_blocks", "admit_s", "dispatch_s", "harvest_s", "tick_s",
             "row_steps", "row_steps_live")
MODEL_KEYS = ("experts_touched", "experts_total", "expert_load_max",
              "tokens_committed", "commit_row_steps", "denoise_row_steps",
              "attended_tokens", "prefill_tokens")
CONTROLS = ("fp8", "no_commit")
NEED = (("models", "sdar.py"), ("serve", "engine_model.py"))


def kernel_predicates(cfg: dict) -> dict:
    """How the trace prints this model's Pallas kernels (told apart by
    what they return, as in `planes/serve.py`): a block step's attention
    is the paged decode kernel on `B x H` query heads of one row against
    the folded pool, so it gives `[slots, B * H, KV * head_dim]`; the
    append gives its pools back (aliased)."""
    m, e, a = cfg["model"], cfg["engine"], cfg["assumed"]
    attn = (f"bf16[{e['slots']},"
            f"{a['block_length'] * m['num_attention_heads']},"
            f"{m['num_key_value_heads'] * m['head_dim']}]")

    def is_kernel(n):
        return "custom-call(" in n and "tpu_custom_call" in n

    return {
        "paged_decode": lambda n: is_kernel(n) and n.split("=", 1)[1]
        .lstrip().startswith(attn),
        "paged_append": lambda n: is_kernel(n)
        and "output_to_operand_aliasing" in n,
    }


def model_config(m: dict, assumed: dict, dtype):
    from ray_tpu.models import sdar

    assert m["model_type"] == "sdar_moe" and m["hidden_act"] == "silu"
    assert not m["attention_bias"] and not m["tie_word_embeddings"]
    assert assumed["remasking_strategy"] == "low_confidence_dynamic"
    return sdar.SdarMoeConfig.from_hf(
        m, block_length=assumed["block_length"], mask_id=assumed["mask_id"],
        denoising_steps=assumed["denoising_steps"],
        confidence_threshold=assumed["confidence_threshold"], dtype=dtype)


def fp8_weights(params: dict) -> dict:
    """The control: every matmul weight of the layers (q, k, v, o and
    every expert) rounded to float8 (e4m3, one scale a matrix, an
    expert's each its own) and held in bfloat16 again.  The router
    (float32 by the configuration), norms, embedding and head stay."""
    import jax
    import jax.numpy as jnp

    from benchmarks import weights_sdar as wts
    from benchmarks.planes.serve_latent_moe import round_e4m3

    @jax.jit
    def round_trip(w):
        w32 = w.astype(jnp.float32)
        amax = jnp.max(jnp.abs(w32), axis=(-2, -1), keepdims=True)
        scale = 448.0 / jnp.maximum(amax, 1e-30)
        return (round_e4m3(w32 * scale) / scale).astype(w.dtype)

    return {**params, "layers": {
        k: round_trip(v) if k in wts.MATRICES else v
        for k, v in params["layers"].items()}}


def sample_answers(served: list, sample: int, full: int, seed: int) -> list:
    """Which of the served `(prompt, answer, decided_at, steps)` the
    reference reads: a seeded permutation, dealt round-robin over the
    (PROMPT LENGTH, STEPS) pairs (3 x 3 in the cell), and within a
    pair's queue the answers of `full` tokens before any shorter one (a
    caller's first answer is short: `first_output_step`)."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 0xC0DE])
    by_kind = {}
    for i in rng.permutation(len(served)):
        p, a, _, steps = served[i]
        by_kind.setdefault((len(p), steps), []).append(int(i))
    queues = [sorted(q, key=lambda i: len(served[i][1]) < full)
              for _, q in sorted(by_kind.items())]
    pick = []
    while len(pick) < sample and any(queues):
        for q in queues:
            if q and len(pick) < sample:
                pick.append(q.pop(0))
    return pick


class BenchBlockDiffusionService(base.BenchLlamaService):
    """`BenchLlamaService` with a block-diffusion model behind the
    engine and `denoising_steps` in a request's body."""

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

        from benchmarks import weights_sdar as wts
        from ray_tpu.core.accelerators import device_report
        from ray_tpu.serve import engine_model
        from ray_tpu.serve.llm_engine import LlamaEngine

        self._jax = jax
        self._compiles = _common.count_compiles()
        self.device = device_report()  # first touch of JAX
        timing = {"jax_start_s": time.perf_counter() - t0}
        m, e, a = cfg["model"], cfg["engine"], cfg["assumed"]
        control = opts.get("control")
        if control and control not in CONTROLS:
            raise ValueError(f"this plane's controls are {CONTROLS}, not "
                             f"{control!r}")
        # the rehearsal runs in float32: its limits then tell a sound
        # program from a control at toy sizes too
        self._dtype = dtype = (jnp.float32 if opts.get("rehearse")
                               else jnp.bfloat16)
        self.mcfg = model_config(m, a, dtype)
        std = float(a["initializer_range"])
        params = wts.params(m, self.seed, dtype=dtype, std=std)
        if control == "fp8":
            params = fp8_weights(params)
        jax.block_until_ready(params)
        timing["weights_s"] = time.perf_counter() - t0
        # `no_commit`: a block is output with its last decision, and the
        # rows its last denoising forward wrote stay (this process
        # serves nothing else)
        engine_model.BlockDiffusionEngineModel.commit = control != "no_commit"
        self.engine = LlamaEngine(
            self.mcfg, params, slots=e["slots"], max_len=e["max_len"],
            chunk=e["chunk"], block_size=e["block_size"],
            kv_blocks=e["kv_blocks"], prefix_cache=e["prefix_cache"])
        del params
        timing["engine_s"] = time.perf_counter() - t0
        self.plan = base.warmup_plan(mix, e)
        self._warm()
        timing["warm_s"] = time.perf_counter() - t0
        timing["compiles_in_setup"] = len(self._compiles)
        self._served = []      # (prompt, answer, decided_at, steps)
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

    async def __call__(self, request):
        import asyncio

        body = request.json()
        prompt = body["tokens"][0]
        steps = body.get("denoising_steps")
        t0 = time.perf_counter()
        out = await asyncio.wrap_future(self.engine.submit(
            list(prompt), int(body["max_new_tokens"]),
            denoising_steps=steps))
        dt = time.perf_counter() - t0
        self._served.append((prompt, list(out), out.decided_at,
                             steps or self.mcfg.denoising_steps))
        return {"tokens": [list(out)], "engine_s": dt, "replica": self.rid,
                "forwards": out.forwards}

    def _cmd_finish(self, cmd):
        """After the window, engine idle: counters, trace, then the
        engine's memory back and the reference."""
        out = {"rid": self.rid, "device": dict(self.device),
               "served": len(self._served)}
        t = getattr(self, "_trace_thread", None)
        if t is not None:
            t.join(timeout=120)
        stats = self.engine.stats()
        out["engine"] = {k: v for k, v in stats.items() if k != "tick_ring"}
        out["tick_ring"] = [
            {**{k: r[k] for k in TICK_KEYS},
             **{k: r[k] for k in MODEL_KEYS if k in r}}
            for r in stats.get("tick_ring", [])]
        w0, _ = self._window or (0.0, 0.0)
        out["compiles_in_window"] = [
            c for c in self._compiles if w0 <= c[0] <= cmd["wall_end"]]
        out["ttft_p90_polls_s"] = [v for _, v in self._ttft_polls]
        ms = _common.memory_stats()
        out["memory_peak_bytes"] = int(ms.get("peak_bytes_in_use", 0))
        out["memory_limit_bytes"] = int(ms.get("bytes_limit", 0))
        if self._trace is not None:
            from benchmarks import trace_reduce, trace_scopes

            out["trace"] = trace_reduce.reduce_dir(
                self._trace["dir"], annotations=base.ENGINE_SPANS,
                default_gap="engine loop, unattributed",
                kernels=kernel_predicates(self.cfg))
            out["trace"]["scopes"] = trace_scopes.scope_seconds(
                self._trace["dir"], SCOPES, ("jit_decode_chunk_",))
            # when the trace ran, on the tick ring's clock: the readers
            # take their counts from the ticks of that span
            out["trace"]["wall_span"] = [self._trace["wall_start"],
                                         self._trace["wall_stop"]]
            keep = cmd.get("keep_trace_to")
            if keep:
                trace_reduce.copy_xplane(self._trace["dir"], keep)
        out["check"] = self._reference_check(int(cmd.get("sample", 9)))
        self._write(f"result_{self.rid}.json", out)

    # -- `correct`: what the timed path produced, replayed ---------------
    def _reference_check(self, sample: int) -> dict:
        """A seeded sample of this replica's own answers over every
        prompt length and step count, WHOLE sequences with the
        `decided_at` the engine returned, teacher-forced through the
        float32 reference's replay (`reference/sdar.py`: the sequence
        laid out clean and noisy, one row a (sequence, step)), one layer
        at a time, each layer's weights made again from the seed.  The
        engine's weights and cache are given back first.  -> the TOKEN
        margin at every answer position at the step it was decided (the
        reference's largest logit less its logit of the served token)
        and the CHOICE margin of every (block, step) that had to choose
        (`ref.choice_margin`, on the reference's LOG confidence, so that
        it reads on the logits' scale)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks import weights_sdar as wts
        from benchmarks.reference import sdar as ref

        m, a = self.cfg["model"], self.cfg["assumed"]
        served = list(self._served)
        if not served:
            return {"sampled": 0, "tokens": 0}
        t0 = time.perf_counter()
        # the engine is idle and has told what it had to tell
        self.engine.shutdown()
        self.engine.params = self.engine._cache = None
        B, mask_id = int(a["block_length"]), int(a["mask_id"])
        full = max(len(s[1]) for s in served)
        pick = sample_answers(served, sample, full, self.seed)
        longest = max(len(served[i][0]) + len(served[i][1]) for i in pick)
        T = base._cdiv(longest, 128) * 128
        rows = [(i, s) for i in pick
                for s in range(max(served[i][2]) + 1)]
        toks = np.stack([ref.replay_tokens(
            served[i][0], served[i][1], served[i][2], B, mask_id, s, T)
            for i, s in rows])
        span = base._cdiv(full, B) * B
        starts = np.array([T + len(served[i][0]) for i, _ in rows], np.int32)
        starts = np.minimum(starts, 2 * T - span)   # the slice has to fit
        answers = np.zeros((len(rows), span), np.int32)
        for r, (i, _) in enumerate(rows):
            at = T + len(served[i][0]) - starts[r]
            answers[r, at:at + len(served[i][1])] = served[i][1]
        mask, pos = ref.replay_mask(T, B)
        kw = ref.layer_kwargs(m)

        one_layer = jax.jit(lambda x, w: jax.lax.map(
            lambda xb: ref.layer(xb, w, mask=mask, pos=pos, **kw), x),
            donate_argnums=0)

        @jax.jit
        def tail(x, start, answer, ends):
            def one(args):
                xb, s, ans = args
                lg = ref.head(jax.lax.dynamic_slice_in_dim(xb, s, span, 0),
                              ends["final_norm"], ends["lm_head"],
                              m["rms_norm_eps"])
                logc = jnp.max(lg, -1) - jax.nn.logsumexp(lg, axis=-1)
                return ref.margins(lg, ans), logc, jnp.std(lg)
            return jax.lax.map(one, (x, start, answer))

        std = float(a["initializer_range"])
        ends = wts.ends(m, self.seed, self._dtype, std)
        x = jax.jit(lambda t, e: jax.vmap(
            lambda tt: ref.embed(tt, e))(t))(jnp.asarray(toks), ends["tok_emb"])
        for l in range(m["num_hidden_layers"]):
            x = one_layer(x, wts.layer(m, self.seed, l, self._dtype, std))
        marg, logc, lstd = (np.asarray(v) for v in tail(
            x, jnp.asarray(starts), jnp.asarray(answers), ends))
        token, choice = [], []
        for i in pick:
            p, ans, dec, steps = served[i]
            mine = [r for r, (j, _) in enumerate(rows) if j == i]
            at = T + len(p) - starts[mine[0]]
            dec = np.asarray(dec)
            conf = np.stack([logc[r, at:at + len(ans)] for r in mine])
            token += [marg[mine[d], at + k] for k, d in enumerate(dec)]
            choice += ref.choice_margin(conf, dec, B, steps, len(p))
        token, choice = np.asarray(token), np.asarray(choice or [0.0])
        return {
            "sampled": int(len(pick)), "tokens": int(token.size),
            "kinds": sorted({(len(served[i][0]), served[i][3])
                             for i in pick}),
            "rows": len(rows), "sequence_tokens": int(T),
            "max_margin": float(token.max()),
            "mean_margin": float(token.mean()),
            "flipped_share": float((token > 0).mean()),
            "choices": int(len(choice)),
            "max_choice_margin": float(choice.max()),
            "mean_choice_margin": float(choice.mean()),
            "choices_flipped_share": float((choice > 0).mean()),
            "logit_std": float(lstd.mean()),
            "seconds": time.perf_counter() - t0,
        }


def verdict(ctx: dict, cfg: dict) -> dict:
    """`planes/serve.py`'s rows (the token margins, the sample's size,
    the cut) and the choice margins beside them."""
    import math

    v = base.verdict(ctx, cfg)
    lim = cfg["reference"]
    checks = [r["check"] for r in ctx["replicas"] if r["check"]["sampled"]]
    n = sum(c["choices"] for c in checks)
    mean = (sum(c["mean_choice_margin"] * c["choices"] for c in checks) / n
            if n else math.inf)
    worst = max((c["max_choice_margin"] for c in checks), default=math.inf)
    rows = v["rows"] + [
        ("mean_choice_margin_below_reference", mean,
         lim["mean_choice_margin_limit"]),
        ("max_choice_margin_below_reference", worst,
         lim["max_choice_margin_limit"])]
    return {"rows": rows, "correct": all(x <= l for _, x, l in rows)}


def run(cell: dict, cfg: dict, mix: dict, args, t_process_start: float) -> dict:
    """`planes/serve.py`'s `run` with this plane's deployment: that
    function deploys the class its module names, so the name is lent
    for the call (in the cell's process; the replicas unpickle the
    class from this module)."""
    # a program without the model (the parent of the PR that added it)
    # must fail HERE, at once: a replica that cannot import its model
    # is restarted until `serve.run` times out, a quarter of an hour on
    missing = [p for p in (os.path.join(REPO, "ray_tpu", *q) for q in NEED)
               if not os.path.exists(p)]
    if missing:
        raise RuntimeError(
            f"this checkout cannot run {cell['name']}: it has no "
            f"{', '.join(os.path.relpath(p, REPO) for p in missing)}")
    lent = base.BenchLlamaService
    base.BenchLlamaService = BenchBlockDiffusionService
    try:
        return base.run(cell, cfg, mix, args, t_process_start)
    finally:
        base.BenchLlamaService = lent
