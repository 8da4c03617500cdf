"""The serve plane for a decoder whose full layers SELECT what they
attend (`ray_tpu.models.dots3`: latent attention with a learned sparse
selection, window layers of other widths, one chip's share of a wide
expert layer), under traffic that asks a few long documents many short
questions: the same path a user's request takes as in `planes/serve.py`
(`serve.run` -> HTTP proxy -> router -> replica -> `LlamaEngine`), the
same client, side channel and window, imported from there.  What
differs is what the replica builds, warms and checks:

- the model's config and seeded weights (`weights_dots3.py`), the
  engine with a prefill chunk;
- set-up makes the mix's DOCUMENTS resident (each admitted chunk by
  chunk once, so the radix cache holds them before `setup_s` ends) and
  warms the programs the window runs: the chunked admission, the three
  suffix programs behind a 16,384-token hit, the decode chunk at the
  one table width the contexts reach;
- after the window it holds that every request of the window was a
  prefix hit of the whole document, and holds a sample of its own
  answers (over several documents, whole sequences) to the plain
  float32 reference (`reference/dots3.py`), the engine's memory given
  back first: a 16.8k-token sequence in float32 needs the room;
- the trace's device time under the model's scopes, the tick ring's
  selection, window and expert counters.

The context says `"plane": "serve"`: `cell.py` treats it as the serve
plane it is.
"""

from __future__ import annotations

import math
import os
import threading
import time

from benchmarks import loadgen
from benchmarks.manifest import REPO
from benchmarks.planes import _common
from benchmarks.planes import serve as base

# the parts of a step the program marks with `jax.named_scope`
SCOPES = ("dsa_index", "dsa_select", "dsa_attn", "swa_attn", "attn_gate",
          "moe_router", "moe_routed", "moe_shared", "dense_mlp")
TICK_KEYS = ("seq", "t_wall", "admitted", "active", "queued", "live_tokens",
             "gather_blocks", "admit_s", "dispatch_s", "harvest_s",
             "row_steps", "row_steps_live")
MODEL_KEYS = ("experts_touched", "experts_total", "expert_load_max",
              "experts_held", "dsa_selected_share", "window_rows_live",
              "prefix_hit_tokens", "prefill_tokens")
CONTROLS = ("fp8", "dense_full", "window_off")
NEED = (("models", "dots3.py"), ("serve", "engine_model.py"))


def model_config(m: dict, dep: dict, dtype, control=None):
    from ray_tpu.models import dots3

    assert m["scoring_func"] == "sigmoid" and m["norm_topk_prob"]
    assert m["moe_layer_freq"] == 1 and m["rope_scaling"] is None
    assert m["attention_gate_type"] == m["swa_attention_gate_type"] == "headwise"
    assert len(m["layer_types"]) == m["num_hidden_layers"]
    everything = m["max_position_embeddings"]
    return dots3.Dots3Config(
        vocab_size=m["vocab_size"], max_seq_len=m["max_position_embeddings"],
        dim=m["hidden_size"], layer_types=tuple(m["layer_types"]),
        n_heads=m["num_attention_heads"], q_lora_rank=m["q_lora_rank"],
        kv_lora_rank=m["kv_lora_rank"], qk_nope_dim=m["qk_nope_head_dim"],
        qk_rope_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_theta=float(m["rope_theta"]),
        index_n_heads=m["index_n_heads"], index_head_dim=m["index_head_dim"],
        index_topk=(everything if control == "dense_full"
                    else m["index_topk"]),
        swa_n_heads=m["swa_num_attention_heads"],
        swa_q_lora_rank=m["swa_q_lora_rank"],
        swa_kv_lora_rank=m["swa_kv_lora_rank"],
        swa_qk_nope_dim=m["swa_qk_nope_head_dim"],
        swa_qk_rope_dim=m["swa_qk_rope_head_dim"],
        swa_v_head_dim=m["swa_v_head_dim"],
        swa_rope_theta=float(m["swa_rope_theta"]),
        window=(everything if control == "window_off"
                else m["sliding_window_size"]),
        qkv_rescale=m["apply_mla_qkv_lora_rescale"],
        intermediate=m["intermediate_size"],
        moe_intermediate=m["moe_intermediate_size"],
        n_routed_experts=dep["router_experts"],
        experts_held=m["n_routed_experts"],
        expert_offset=dep["expert_offset"],
        n_shared_experts=m["n_shared_experts"],
        top_k=m["num_experts_per_tok"],
        first_k_dense=m["first_k_dense_replace"],
        routed_scale=float(m["routed_scaling_factor"]),
        norm_eps=m["rms_norm_eps"], dtype=dtype)


def fp8_weights(params: dict) -> dict:
    """The control: every matmul weight of the layers (attention and
    indexer projections, the gate, dense MLP, shared and routed
    experts) rounded to float8 (e4m3, one scale a matrix, an expert's
    each its own) and held in bfloat16 again.  The router (float32 by
    the configuration), norms, biases, embedding and head stay."""
    import jax
    import jax.numpy as jnp

    from benchmarks.planes.serve_latent_moe import round_e4m3

    @jax.jit
    def round_trip(w):
        w32 = w.astype(jnp.float32)
        amax = jnp.max(jnp.abs(w32), axis=(-2, -1), keepdims=True)
        scale = 448.0 / jnp.maximum(amax, 1e-30)
        return (round_e4m3(w32 * scale) / scale).astype(w.dtype)

    return {**params, "layers": [
        {k: round_trip(v) if v.ndim >= 2 and k != "router" else v
         for k, v in layer.items()} for layer in params["layers"]]}


def documents(mix: dict, seed: int, vocab: int) -> list:
    """The mix's shared prefixes as the client will send them this
    seed, in the order its schedule first uses them."""
    n = int(mix["shared_prefix"]["len"])
    seen, docs = set(), []
    for reqs in loadgen.closed_loop_schedule(mix, seed, vocab):
        for r in reqs:
            key = tuple(r.prompt[:n])
            if len(r.prompt) > n and key not in seen:
                seen.add(key)
                docs.append(list(key))
    return docs


def sample_answers(served: list, doc: int, sample: int, span: int,
                   seed: int) -> list:
    """Which of the served `(prompt, answer)` pairs the reference
    reads: a seeded permutation, dealt round-robin over the documents
    (as many of them as there are), and within a document's queue the
    answers at least `span` tokens long BEFORE any shorter one.  Most of
    a window's answers are the callers' short FIRST ones
    (`first_output_step`); drawn blindly, 8 picks summed under the
    `min_tokens` the verdict holds in 1.6-3.5% of runs whatever the
    program served (PERF.md section 6, PR 42 and 49).  So the sample is
    `sample x span` tokens in every run, and falls back to a shorter
    answer only where a document has no full one."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 0xC0DE])
    by_doc = {}
    for i in rng.permutation(len(served)):
        by_doc.setdefault(tuple(served[i][0][:doc][:64]), []).append(int(i))
    # a stable sort: full answers first, each kind in the drawn order
    queues = [sorted(q, key=lambda i: len(served[i][1]) < span)
              for q in by_doc.values()]
    pick = []
    while len(pick) < sample and any(queues):
        for q in queues:
            if q and len(pick) < sample:
                pick.append(q.pop(0))
    return pick


class BenchSparseLatentService(base.BenchLlamaService):
    """`BenchLlamaService` with another model behind the engine and the
    documents resident before it is ready."""

    def __init__(self, cfg: dict, mix: dict, seed: int, opts: dict):
        t0 = time.perf_counter()
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.rid = str(os.getpid())
        self.dir = opts["bench_dir"]
        if opts.get("rehearse"):
            import jax

            jax.config.update("jax_platforms", "cpu")
        import jax
        import jax.numpy as jnp

        from benchmarks import weights_dots3 as wts
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
        # a rehearsal runs in float32: the CPU has no fast bfloat16
        dtype = jnp.float32 if opts.get("rehearse") else jnp.bfloat16
        self.mcfg = model_config(m, dep, dtype, control)
        params = wts.params(m, dep, self.seed, dtype=dtype,
                            std=cfg["assumed"]["initializer_range"])
        if control == "fp8":
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
        # what `planes/serve.run` sends over HTTP before the window: a
        # short unshared prompt, a shape `_warm` has run
        self.plan = {"alone": [], "together": [(64, 2)], "widths": []}
        self._docs = documents(mix, self.seed, m["vocab_size"])
        self._warm()
        timing["warm_s"] = time.perf_counter() - t0
        timing["documents"] = len(self._docs)
        timing["compiles_in_setup"] = len(self._compiles)
        self._served = []      # (prompt, output) of every answer
        self._window = None    # (wall start, seconds)
        self._at_window = None  # the engine's counters when it opened
        self._ttft_polls = []
        self._trace = None
        self._seen = set()
        self._stop = False
        threading.Thread(target=self._side_channel, name="bench-side",
                         daemon=True).start()
        self._write(f"ready_{self.rid}.json", {
            "rid": self.rid, "device": self.device, "timing": timing,
            "plan": self.plan, "wall_ready": time.time()})

    # -- warm-up: the documents resident, every shape of the window ----
    def _warm(self):
        import numpy as np

        rng = np.random.default_rng([self.seed, 0xA11])
        V = self.cfg["model"]["vocab_size"]
        doc_len = int(self.mix["shared_prefix"]["len"])
        questions = [p - doc_len for p in
                     loadgen.possible_lengths(self.mix["prompt_len"])]

        def ask(doc, n, out):
            return self.engine.submit(
                list(doc) + rng.integers(1, V, size=n).tolist(), out)

        # each document once, chunk by chunk, behind a first question;
        # then every question length behind a hit, alone and together
        for doc in self._docs:
            ask(doc, questions[0], 2).result(timeout=900)
        for n in questions:
            ask(self._docs[0], n, 2 * self.cfg["engine"]["chunk"]
                ).result(timeout=900)
        for f in [ask(self._docs[i % len(self._docs)], n, 2)
                  for i, n in enumerate(questions)]:
            f.result(timeout=900)
        for p, o in self.plan["together"]:
            self.engine.submit(rng.integers(1, V, size=p).tolist(),
                               o).result(timeout=900)

    def _cmd_window(self, cmd):
        self._at_window = self._counters()
        self._window = (cmd["wall_start"], cmd["seconds"])

    def _counters(self) -> dict:
        s = self.engine.stats()
        return {k: s.get(k, 0) for k in (
            "prefix_hits", "prefix_hit_tokens", "prefill_rows",
            "prefill_tokens", "blocks_cached")}

    def _cmd_finish(self, cmd):
        """After the window, engine idle: counters, trace, the hits,
        then the engine's memory back and the reference."""
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
                default_gap="engine loop, unattributed")
            sc = trace_scopes.scope_seconds(
                self._trace["dir"], SCOPES, ("jit_decode_chunk_",))
            out["trace"]["scopes"] = sc
            # `decode_step_ms` looks for the programs that hold the
            # kernel labelled `paged_decode`; this model's decode
            # attention is plain XLA, so the programs are found by the
            # name the engine gives them and the attention by its scopes
            if sc.get("program_calls"):
                out["trace"]["kernels"] = {"paged_decode": {
                    "seconds": sc["programs_s"],
                    "calls": sc["program_calls"],
                    "op_seconds": sc.get("dsa_attn", 0.0)
                    + sc.get("swa_attn", 0.0),
                    "op_calls": sc["program_calls"]
                    * self.cfg["engine"]["chunk"]
                    * self.cfg["model"]["num_hidden_layers"]}}
            keep = cmd.get("keep_trace_to")
            if keep:
                trace_reduce.copy_xplane(self._trace["dir"], keep)
        out["hits"] = self._hits()
        out["check"] = self._reference_check(int(cmd.get("sample", 8)))
        self._write(f"result_{self.rid}.json", out)

    def _hits(self) -> dict:
        """What the window's admissions were: every one has to be a
        prefix hit of the whole document (the documents were made
        resident in set-up and are never evicted inside a window)."""
        then, now = self._at_window or self._counters(), self._counters()
        d = {k: now[k] - then[k] for k in now}
        doc = int(self.mix["shared_prefix"]["len"])
        return {"requests": d["prefill_rows"], "hits": d["prefix_hits"],
                "hit_tokens": d["prefix_hit_tokens"],
                "prefill_tokens": d["prefill_tokens"],
                "document_tokens": doc,
                "not_a_whole_hit": (d["prefill_rows"] - d["prefix_hits"])
                + abs(d["prefix_hit_tokens"] - doc * d["prefix_hits"]) // doc}

    # -- `correct`: the served tokens against the plain reference -------
    def _reference_check(self, sample: int) -> dict:
        """A seeded sample of this replica's own answers over several
        documents, WHOLE sequences (document, question, answer),
        teacher-forced through the float32 reference one layer at a
        time, each layer's weights made again from the seed; the
        engine's weights and cache are given back first."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks import weights_dots3 as wts
        from benchmarks.reference import dots3 as ref

        m, dep, lim = self.cfg["model"], self.cfg["deployment"], \
            self.cfg["reference"]
        std = self.cfg["assumed"]["initializer_range"]
        doc = int(self.mix["shared_prefix"]["len"])
        # the window's answers: those behind a document (the warm-up
        # over HTTP sent short prompts)
        served = [s for s in self._served if len(s[0]) > doc]
        if not served:
            return {"sampled": 0, "tokens": 0}
        t0 = time.perf_counter()
        # the engine is idle and has told what it had to tell
        self.engine.shutdown()
        self.engine.params = self.engine._cache = None
        span = int(lim["positions"])  # the last <= span answers
        pick = sample_answers(served, doc, sample, span, self.seed)
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

            def run(x, w, s0):
                return jax.lax.map(
                    lambda a: ref.layer(a[0], w, qblock=qblock,
                                        overlap_rows=(a[1], span), **kw),
                    (x, s0))
            return jax.jit(run, donate_argnums=0)

        @jax.jit
        def tail(x, start, answer, ends):
            def one(args):
                xb, s, a = args
                lg = ref.head(jax.lax.dynamic_slice_in_dim(xb, s, span, 0),
                              ends["final_norm"], ends["lm_head"],
                              m["rms_norm_eps"])
                return ref.margins(lg, a), jnp.std(lg)
            return jax.lax.map(one, (x, start, answer))

        ends = wts.ends(m, self.seed, std=std)
        x = jax.jit(lambda t, e: jax.vmap(
            lambda tt: ref.embed(tt, e))(t))(jnp.asarray(toks), ends["tok_emb"])
        s0 = jnp.asarray(starts, jnp.int32)
        overlaps = []
        for l in range(m["num_hidden_layers"]):
            x, ov = one_layer(l)(x, wts.layer(m, dep, self.seed, l, std=std),
                                 s0)
            ov = np.asarray(ov)
            if not np.isnan(ov).all():
                overlaps.append(float(np.nanmean(ov)))
        marg, lstd = tail(x, s0, jnp.asarray(answers), ends)
        marg = np.asarray(marg)
        vals = np.concatenate([marg[r, o:o + c]
                               for r, (o, c) in enumerate(zip(offs, counts))])
        return {
            "sampled": int(len(pick)), "tokens": int(vals.size),
            "documents": len({tuple(served[i][0][:64]) for i in pick}),
            "sequence_tokens": int(T),
            "max_margin": float(vals.max()),
            "mean_margin": float(vals.mean()),
            "flipped_share": float((vals > 0).mean()),
            "logit_std": float(np.asarray(lstd).mean()),
            "index_select_overlap": (min(overlaps) if overlaps else None),
            "seconds": time.perf_counter() - t0,
        }


def verdict(ctx: dict, cfg: dict) -> dict:
    """`correct`: `planes/serve.py`'s rows, and beside them that every
    request of the window was a prefix hit of its whole document and
    that the sample reached over enough documents."""
    out = base.verdict(ctx, cfg)
    lim = cfg["reference"]
    checks = [r["check"] for r in ctx["replicas"] if r["check"]["sampled"]]
    misses = sum(r.get("hits", {}).get("not_a_whole_hit", math.inf)
                 for r in ctx["replicas"])
    docs = min((c.get("documents", 0) for c in checks), default=0)
    out["rows"] += [
        ("window_requests_not_a_whole_document_hit", misses, 0),
        ("sampled_documents_at_least", -docs, -lim["documents"]),
    ]
    out["correct"] = all(v <= l for _, v, l in out["rows"])
    return out


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
    base.BenchLlamaService = BenchSparseLatentService
    try:
        return base.run(cell, cfg, mix, args, t_process_start)
    finally:
        base.BenchLlamaService = lent
