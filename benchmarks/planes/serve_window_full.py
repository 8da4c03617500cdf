"""The serve plane for a decoder whose WINDOW layers keep their rows in a
per-slot ring beside full layers that page theirs
(`ray_tpu.models.mimo_v2`: grouped-query attention of two head counts,
keys 192 and values 128 wide, a learned sink in the window layers, one
chip's share of a wide expert layer), under a queue that mixes short and
long prompts: the same path a user's request takes as in
`planes/serve.py` (`serve.run` -> HTTP proxy -> router -> replica ->
`LlamaEngine`), the same client, warm-up plan, side channel, window and
verdict, imported from there.  What differs is what the replica builds
and checks:

- the model's config and seeded weights (`weights_mimo_v2.py`), the
  engine with a prefill chunk (a prompt past it is admitted chunk by
  chunk, the slot's ring carried from chunk to chunk);
- after the window it holds a sample of its own answers, over every
  prompt length of the mix (a packed admission, two chunks, four), WHOLE
  sequences, to the plain float32 reference (`reference/mimo_v2.py`),
  the engine's memory given back first;
- the three controls; the shape its paged kernel prints in a trace; the
  scopes' device time in the decode and in the prefill programs; the
  tick ring's counters of both cache kinds and of the held experts.

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
from benchmarks.planes.serve import verdict  # noqa: F401  (the plane's)

# the parts of a step the program marks with `jax.named_scope`
SCOPES = ("full_attn", "swa_attn", "swa_ring_write", "moe_router",
          "moe_routed", "dense_mlp")
TICK_KEYS = ("seq", "t_wall", "admitted", "active", "queued", "live_tokens",
             "gather_blocks", "admit_s", "dispatch_s", "harvest_s",
             "row_steps", "row_steps_live", "state_rows_live")
MODEL_KEYS = ("experts_touched", "experts_total", "expert_load_max",
              "experts_held", "window_rows_live", "ring_bytes_live",
              "full_cache_tokens_live", "prefill_tokens")
CONTROLS = ("window_off", "sink_off", "fp8")
# `window_off`: a ring holds no more than its rows, so "the window
# layers see everything" is the window AND the ring widened by this
WINDOW_OFF_FACTOR = 4
NEED = (("models", "mimo_v2.py"), ("serve", "engine_model.py"))


def kernel_predicates(cfg: dict) -> dict:
    """How the trace prints this model's Pallas kernels (told apart by
    what they return, as in `planes/serve.py`): the paged decode
    attention of a full layer gives `[slots, heads, KV * 128]` (a
    token's value heads lie side by side in one pool row, and the kernel
    returns the row's width); the append gives its pools back
    (aliased)."""
    m, e = cfg["model"], cfg["engine"]
    attn = (f"bf16[{e['slots']},{m['num_attention_heads']},"
            f"{m['num_key_value_heads'] * m['v_head_dim']}]")

    def is_kernel(n):
        return "custom-call(" in n and "tpu_custom_call" in n

    return {
        "paged_decode": lambda n: is_kernel(n) and n.split("=", 1)[1]
        .lstrip().startswith(attn),
        "paged_append": lambda n: is_kernel(n)
        and "output_to_operand_aliasing" in n,
    }


def model_config(m: dict, dep: dict, dtype, control=None):
    from ray_tpu.models import mimo_v2

    assert m["model_type"] == "mimo_v2" and m["scoring_func"] == "sigmoid"
    assert m["norm_topk_prob"] and m["topk_method"] == "noaux_tc"
    assert m["n_group"] == m["topk_group"] == 1 and not m["n_shared_experts"]
    assert m["attention_projection_layout"] == "fused_qkv"
    assert not m["attention_bias"] and not m["tie_word_embeddings"]
    assert m["rope_scaling"]["rope_type"] == "default"
    assert m["swa_head_dim"] == m["head_dim"]
    assert m["swa_v_head_dim"] == m["v_head_dim"]
    assert m["sliding_window"] == m["sliding_window_size"]
    assert len(m["hybrid_layer_pattern"]) == m["num_hidden_layers"]
    assert len(m["moe_layer_freq"]) == m["num_hidden_layers"]
    window = m["sliding_window"]
    if control == "window_off":
        window *= WINDOW_OFF_FACTOR
    return mimo_v2.MimoV2Config(
        vocab_size=m["vocab_size"], max_seq_len=m["max_position_embeddings"],
        dim=m["hidden_size"],
        layer_pattern=tuple(m["hybrid_layer_pattern"]),
        moe_layers=tuple(m["moe_layer_freq"]), head_dim=m["head_dim"],
        v_head_dim=m["v_head_dim"],
        rotary_dim=int(m["head_dim"] * m["partial_rotary_factor"]),
        value_scale=float(m["attention_value_scale"]),
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        rope_theta=float(m["rope_theta"]),
        full_sink=bool(m["add_full_attention_sink_bias"]),
        swa_n_heads=m["swa_num_attention_heads"],
        swa_n_kv_heads=m["swa_num_key_value_heads"],
        swa_rope_theta=float(m["swa_rope_theta"]), window=window,
        swa_sink=(bool(m["add_swa_attention_sink_bias"])
                  and control != "sink_off"),
        intermediate=m["intermediate_size"],
        moe_intermediate=m["moe_intermediate_size"],
        n_routed_experts=dep["router_experts"],
        experts_held=m["n_routed_experts"],
        expert_offset=dep["expert_offset"],
        top_k=m["num_experts_per_tok"],
        routed_scale=float(m["routed_scaling_factor"] or 1.0),
        norm_eps=m["layernorm_epsilon"], dtype=dtype)


def sample_answers(served: list, sample: int, span: int, seed: int) -> list:
    """Which of the served `(prompt, answer)` pairs the reference reads:
    a seeded permutation, dealt round-robin over the PROMPT LENGTHS (a
    packed admission, a prompt of two chunks, one of four), and within a
    length's queue the answers at least `span` tokens long before any
    shorter one (a caller's first answer is short: `first_output_step`)."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 0xC0DE])
    by_len = {}
    for i in rng.permutation(len(served)):
        by_len.setdefault(len(served[i][0]), []).append(int(i))
    # a stable sort: full answers first, each kind in the drawn order
    queues = [sorted(q, key=lambda i: len(served[i][1]) < span)
              for _, q in sorted(by_len.items())]
    pick = []
    while len(pick) < sample and any(queues):
        for q in queues:
            if q and len(pick) < sample:
                pick.append(q.pop(0))
    return pick


class BenchWindowFullService(base.BenchLlamaService):
    """`BenchLlamaService` with another model behind the engine."""

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

        from benchmarks import weights_mimo_v2 as wts
        from ray_tpu.core.accelerators import device_report
        from ray_tpu.serve.llm_engine import LlamaEngine

        self._jax = jax
        self._compiles = _common.count_compiles()
        self.device = device_report()  # first touch of JAX
        timing = {"jax_start_s": time.perf_counter() - t0}
        m, e, dep = cfg["model"], cfg["engine"], cfg["deployment"]
        control = opts.get("control")
        if control and control not in CONTROLS:
            raise ValueError(f"this plane's controls are {CONTROLS}, not "
                             f"{control!r}")
        # the rehearsal runs in float32: its limits then tell a sound
        # program from a control at toy sizes too
        self._dtype = dtype = (jnp.float32 if opts.get("rehearse")
                               else jnp.bfloat16)
        self.mcfg = model_config(m, dep, dtype, control)
        params = wts.params(m, dep, self.seed, dtype=dtype, **self._std())
        if control == "fp8":
            from benchmarks.planes.serve_sparse_latent import fp8_weights

            params = fp8_weights(params)
        jax.block_until_ready(params)
        timing["weights_s"] = time.perf_counter() - t0
        self.engine = LlamaEngine(
            self.mcfg, params, slots=e["slots"], max_len=e["max_len"],
            chunk=e["chunk"], block_size=e["block_size"],
            kv_blocks=e["kv_blocks"], prefix_cache=e["prefix_cache"],
            prefill_chunk=e["prefill_chunk"])
        del params
        timing["engine_s"] = time.perf_counter() - t0
        self.plan = base.warmup_plan(mix, e)
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

    def _std(self) -> dict:
        a = self.cfg["assumed"]
        return {"std": float(a["initializer_range"]),
                "sink_std": float(a["sink_std"])}

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
            out["trace"]["prefill_scopes"] = trace_scopes.scope_seconds(
                self._trace["dir"], SCOPES,
                ("jit_prefill_packed_", "jit_prefill_chunk_"))
            keep = cmd.get("keep_trace_to")
            if keep:
                trace_reduce.copy_xplane(self._trace["dir"], keep)
        out["check"] = self._reference_check(int(cmd.get("sample", 8)))
        self._write(f"result_{self.rid}.json", out)

    # -- `correct`: the served tokens against the plain reference -------
    def _reference_check(self, sample: int) -> dict:
        """A seeded sample of this replica's own answers over every
        prompt length, WHOLE sequences (prompt and every answer token),
        teacher-forced through the float32 reference one layer at a
        time, each layer's weights made again from the seed: packed and
        chunked admission and decoding through the blocks and the ring,
        past many wraps of it, against a forward pass that has neither.
        The engine's weights and cache are given back first."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks import weights_mimo_v2 as wts
        from benchmarks.reference import mimo_v2 as ref

        m, dep, lim = (self.cfg["model"], self.cfg["deployment"],
                       self.cfg["reference"])
        served = list(self._served)
        if not served:
            return {"sampled": 0, "tokens": 0}
        t0 = time.perf_counter()
        # the engine is idle and has told what it had to tell
        self.engine.shutdown()
        self.engine.params = self.engine._cache = None
        span = int(lim["positions"])  # the last <= span answers
        pick = sample_answers(served, sample, span, self.seed)
        longest = max(len(served[i][0]) + len(served[i][1]) for i in pick)
        T = max(base._cdiv(longest, 128) * 128, span)
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
        qblock = min(128, T)

        def one_layer(l):
            kw = ref.layer_kwargs(m, l, offset=dep["expert_offset"])
            return jax.jit(lambda x, w: jax.lax.map(
                lambda xb: ref.layer(xb, w, qblock=qblock, **kw), x),
                donate_argnums=0)

        @jax.jit
        def tail(x, start, answer, ends):
            def one(args):
                xb, s, a = args
                lg = ref.head(jax.lax.dynamic_slice_in_dim(xb, s, span, 0),
                              ends["final_norm"], ends["lm_head"],
                              m["layernorm_epsilon"])
                return ref.margins(lg, a), jnp.std(lg)
            return jax.lax.map(one, (x, start, answer))

        std = self._std()
        ends = wts.ends(m, self.seed, self._dtype, std["std"])
        x = jax.jit(lambda t, e: jax.vmap(
            lambda tt: ref.embed(tt, e))(t))(jnp.asarray(toks), ends["tok_emb"])
        for l in range(m["num_hidden_layers"]):
            x = one_layer(l)(x, wts.layer(m, dep, self.seed, l, self._dtype,
                                          **std))
        marg, lstd = tail(x, jnp.asarray(starts, jnp.int32),
                          jnp.asarray(answers), ends)
        marg = np.asarray(marg)
        vals = np.concatenate([marg[r, o:o + c]
                               for r, (o, c) in enumerate(zip(offs, counts))])
        return {
            "sampled": int(len(pick)), "tokens": int(vals.size),
            "prompt_lengths": sorted({len(served[i][0]) for i in pick}),
            "sequence_tokens": int(T),
            "max_margin": float(vals.max()),
            "mean_margin": float(vals.mean()),
            "flipped_share": float((vals > 0).mean()),
            "logit_std": float(np.asarray(lstd).mean()),
            "seconds": time.perf_counter() - t0,
        }


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
    base.BenchLlamaService = BenchWindowFullService
    try:
        return base.run(cell, cfg, mix, args, t_process_start)
    finally:
        base.BenchLlamaService = lent
