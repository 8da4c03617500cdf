"""The serve plane for a latent-attention, mixture-of-experts decoder
(`ray_tpu.models.deepseek_v3`): the same path a user's request takes
as in `planes/serve.py` (`serve.run` -> HTTP proxy -> router -> replica
-> `LlamaEngine`), the same client, warm-up plan, side channel, window
and verdict — all imported from there.  What differs is what the
replica builds and checks: the model's config and seeded weights
(`weights_deepseek_v3.py`), the plain reference its answers are held
to (`reference/deepseek_v3.py`), the control, the shapes its kernels
print in a trace, and the expert counters it ships from the tick ring.

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
SCOPES = ("mla_attn", "moe_router", "moe_routed", "moe_shared", "dense_mlp")
TICK_KEYS = ("seq", "t_wall", "admitted", "active", "queued", "live_tokens",
             "gather_blocks", "admit_s", "dispatch_s", "harvest_s")
EXPERT_KEYS = ("experts_touched", "experts_total", "expert_load_max")


def kernel_predicates(cfg: dict) -> dict:
    """How the trace prints this model's Pallas kernels (told apart by
    what they return, as in `planes/serve.py`): the latent decode
    attention gives `[slots, heads, kv_lora_rank]`, under the label
    `paged_decode` so that `decode_step_ms` reads this cell as it
    stands; the latent append gives its pool back (aliased); a grouped
    expert product of a decode step gives `[slots * top_k, ...]`."""
    m, e = cfg["model"], cfg["engine"]
    attn = (f"bf16[{e['slots']},{m['num_attention_heads']},"
            f"{m['kv_lora_rank']}]")
    pairs = f"bf16[{e['slots'] * m['num_experts_per_tok']},"

    def is_kernel(n):
        return "custom-call(" in n and "tpu_custom_call" in n

    def gives(n, shape):
        return is_kernel(n) and n.split("=", 1)[1].lstrip().startswith(shape)

    return {
        "paged_decode": lambda n: gives(n, attn),
        "paged_append": lambda n: is_kernel(n)
        and "output_to_operand_aliasing" in n,
        "moe_grouped": lambda n: gives(n, pairs),
    }


def model_config(m: dict, dtype):
    from ray_tpu.models import deepseek_v3

    assert m["q_lora_rank"] is None and m["n_group"] == m["topk_group"] == 1
    assert m["scoring_func"] == "sigmoid" and m["norm_topk_prob"]
    assert m["moe_layer_freq"] == 1 and m["rope_interleave"]
    assert m["qk_head_dim"] == m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return deepseek_v3.DeepseekV3Config(
        vocab_size=m["vocab_size"], max_seq_len=m["max_position_embeddings"],
        dim=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], qk_nope_dim=m["qk_nope_head_dim"],
        qk_rope_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        kv_lora_rank=m["kv_lora_rank"], intermediate=m["intermediate_size"],
        moe_intermediate=m["moe_intermediate_size"],
        n_routed_experts=m["n_routed_experts"],
        n_shared_experts=m["n_shared_experts"],
        top_k=m["num_experts_per_tok"],
        first_k_dense=m["first_k_dense_replace"],
        routed_scale=m["routed_scaling_factor"],
        rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
        dtype=dtype)


def round_e4m3(x):
    """float8 (e4m3) rounding of float32 values within +-448, done in
    arithmetic: to the nearest multiple of 2^(exponent - 3), exponents
    from -6 up (below that the format's subnormal step).  Not a cast:
    on the v5e a `float32 -> float8_e4m3fn -> float32` pair inside one
    program came back bit-identical (the first control run, PR 27: the
    same margins as the sound run to 16 digits), while this is the same
    on every backend; a test holds it to the cast on the CPU."""
    import jax.numpy as jnp

    _, ex = jnp.frexp(jnp.abs(x))              # |x| = m * 2^ex, m in [.5, 1)
    step = jnp.exp2(jnp.maximum(ex - 1, -6).astype(jnp.float32) - 3.0)
    return jnp.round(x / step) * step


def fp8_weights(params: dict) -> dict:
    """The control: every matmul weight of the layers (attention
    projections, dense MLP, shared and routed experts) rounded to
    float8 (e4m3, one scale a matrix, an expert's each its own) and
    held in bfloat16 again — the step below the configuration's
    precision that a model whose step is weight reads tempts one to
    take.  The router (float32 by the configuration), norms, embedding
    and head stay as they were."""
    import jax
    import jax.numpy as jnp

    def round_trip(w):  # [layers, ..., in, out], a layer at a time
        def one(wl):
            wl32 = wl.astype(jnp.float32)
            amax = jnp.max(jnp.abs(wl32), axis=(-2, -1), keepdims=True)
            scale = 448.0 / jnp.maximum(amax, 1e-30)
            return (round_e4m3(wl32 * scale) / scale).astype(wl.dtype)
        return jax.lax.map(one, w)

    fn = jax.jit(round_trip, donate_argnums=0)
    out = dict(params)
    for stack in ("dense_layers", "moe_layers"):
        out[stack] = {k: fn(v) if v.ndim >= 3 and k != "router" else v
                      for k, v in params[stack].items()}
    return out


class BenchLatentMoeService(base.BenchLlamaService):
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

        from benchmarks import weights_deepseek_v3 as wts
        from ray_tpu.core.accelerators import device_report
        from ray_tpu.serve.llm_engine import LlamaEngine

        self._jax = jax
        self._compiles = _common.count_compiles()
        self.device = device_report()  # first touch of JAX
        timing = {"jax_start_s": time.perf_counter() - t0}
        m, e = cfg["model"], cfg["engine"]
        self.mcfg = model_config(m, jnp.bfloat16)
        params = wts.params(m, self.seed, std=cfg["assumed"]["initializer_range"])
        if opts.get("control") == "fp8":
            params = fp8_weights(params)
        elif opts.get("control"):
            raise ValueError(f"this plane's control is 'fp8', not "
                             f"{opts['control']!r}")
        jax.block_until_ready(params)
        timing["weights_s"] = time.perf_counter() - t0
        self.engine = LlamaEngine(
            self.mcfg, params, slots=e["slots"], max_len=e["max_len"],
            chunk=e["chunk"], block_size=e["block_size"],
            kv_blocks=e["kv_blocks"], prefix_cache=e["prefix_cache"])
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

    def _cmd_finish(self, cmd):
        """After the window, engine idle: counters, trace, reference.
        As the base class's, with this model's kernel shapes, the
        scopes' device time, and the tick ring's expert counters."""
        out = {"rid": self.rid, "device": dict(self.device),
               "served": len(self._served)}
        t = getattr(self, "_trace_thread", None)
        if t is not None:
            t.join(timeout=120)
        stats = self.engine.stats()
        out["engine"] = {k: v for k, v in stats.items() if k != "tick_ring"}
        out["tick_ring"] = [
            {**{k: r[k] for k in TICK_KEYS if k in r},
             **{k: r[k] for k in EXPERT_KEYS if k in r}}
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
            keep = cmd.get("keep_trace_to")
            if keep:
                trace_reduce.copy_xplane(self._trace["dir"], keep)
        out["check"] = self._reference_check(int(cmd.get("sample", 8)))
        self._write(f"result_{self.rid}.json", out)

    # -- `correct`: the served tokens against the plain reference -------
    def _reference_check(self, sample: int) -> dict:
        """As the base class's: a seeded sample of this replica's own
        answers, teacher-forced through the float32 reference one layer
        at a time, each layer's weights made again from the seed."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks import weights_deepseek_v3 as wts
        from benchmarks.reference import deepseek_v3 as ref

        m = self.cfg["model"]
        std = self.cfg["assumed"]["initializer_range"]
        served = list(self._served)
        if not served:
            return {"sampled": 0, "tokens": 0}
        rng = np.random.default_rng([self.seed, 0xC0DE])
        pick = rng.permutation(len(served))[:sample]
        span = int(self.cfg["reference"]["positions"])  # last <= span answers
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
        kw = ref.layer_kwargs(m)

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
        ends = wts.ends(m, self.seed, std=std)
        x = jax.jit(lambda t, e: jax.vmap(
            lambda tt: ref.embed(tt, e))(t))(jnp.asarray(toks), ends["tok_emb"])
        for l in range(m["num_hidden_layers"]):
            x = one_layer(x, wts.layer(m, self.seed, l, std=std))
        marg, lstd = tail(x, jnp.asarray(starts, jnp.int32),
                          jnp.asarray(answers), ends)
        marg = np.asarray(marg)
        vals = np.concatenate([marg[r, o:o + c]
                               for r, (o, c) in enumerate(zip(offs, counts))])
        return {
            "sampled": int(len(pick)), "tokens": int(vals.size),
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
    need = [os.path.join(REPO, "ray_tpu", *p) for p in (
        ("models", "deepseek_v3.py"), ("serve", "engine_model.py"))]
    missing = [p for p in need if not os.path.exists(p)]
    if missing:
        raise RuntimeError(
            f"this checkout cannot run {cell['name']}: it has no "
            f"{', '.join(os.path.relpath(p, REPO) for p in missing)}")
    lent = base.BenchLlamaService
    base.BenchLlamaService = BenchLatentMoeService
    try:
        return base.run(cell, cfg, mix, args, t_process_start)
    finally:
        base.BenchLlamaService = lent
