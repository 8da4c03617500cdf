"""The serve plane for a HYBRID decoder (`ray_tpu.models.lfm2`: gated
short convolutions beside grouped-query attention, sigmoid-routed
experts): the same path a user's request takes as in `planes/serve.py`
(`serve.run` -> HTTP proxy -> router -> replica -> `LlamaEngine`), the
same client, warm-up plan, side channel, window and verdict, all
imported from there, as `planes/serve_latent_moe.py` does.  What differs
is what the replica builds and checks: the model's config and seeded
weights (`weights_lfm2.py`), the plain reference its answers are held to
(`reference/lfm2.py`), the two controls, the shapes its kernels print in
a trace, and the counters of BOTH cache kinds and of the experts that it
ships from the tick ring.

The context says `"plane": "serve"`: `cell.py` treats it as the serve
plane it is.
"""

from __future__ import annotations

import functools
import os
import threading
import time

from benchmarks.manifest import REPO
from benchmarks.planes import _common
from benchmarks.planes import serve as base
from benchmarks.planes.serve import verdict  # noqa: F401  (the plane's)

# the parts of a step the program marks with `jax.named_scope`
SCOPES = ("short_conv", "gqa_attn", "moe_router", "moe_routed", "dense_mlp")
TICK_KEYS = ("seq", "admitted", "active", "queued", "live_tokens",
             "gather_blocks", "admit_s", "dispatch_s", "harvest_s")
# the per-slot leaves' counters, and the experts': the names the state
# model's and the latent expert model's planes ship them under
MODEL_KEYS = ("t_wall", "state_rows_live", "row_steps_live", "row_steps",
              "experts_touched", "experts_total", "expert_load_max")
CONTROLS = ("fp8", "conv_state_zero")
LANES = 128


def kernel_predicates(cfg: dict) -> dict:
    """How the trace prints this model's Pallas kernels (told apart by
    what they return, as in `planes/serve.py`): the paged decode
    attention gives `[slots, heads, KV * 64]` (a token's heads of 64 lie
    side by side in one pool row, and the kernel returns the row's
    width); the append gives its pools back (aliased); a grouped expert
    product of a decode step gives `[slots * top_k, ...]`."""
    m, e = cfg["model"], cfg["engine"]
    width = cfg["assumed"]["head_dim"]
    if width % LANES:
        width *= m["num_key_value_heads"]
    attn = f"bf16[{e['slots']},{m['num_attention_heads']},{width}]"
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


def model_config(m: dict, assumed: dict, dtype):
    from ray_tpu.models import lfm2

    assert m["model_type"] == "lfm2_moe" and not m["conv_bias"]
    assert m["norm_topk_prob"] and m["use_expert_bias"]
    assert assumed["tie_word_embeddings"]
    assert len(m["layer_types"]) == m["num_hidden_layers"]
    assert assumed["head_dim"] * m["num_attention_heads"] == m["hidden_size"]
    return lfm2.Lfm2MoeConfig(
        vocab_size=m["vocab_size"], max_seq_len=m["max_position_embeddings"],
        dim=m["hidden_size"], layer_types=tuple(m["layer_types"]),
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=assumed["head_dim"],
        intermediate=m["intermediate_size"],
        moe_intermediate=m["moe_intermediate_size"],
        n_experts=m["num_experts"], top_k=m["num_experts_per_tok"],
        n_dense_layers=m["num_dense_layers"],
        routed_scale=float(m["routed_scaling_factor"]),
        conv_L=m["conv_L_cache"], rope_theta=float(m["rope_theta"]),
        norm_eps=m["norm_eps"], route_eps=float(assumed["route_eps"]),
        dtype=dtype)


# matmul weights of the four stacks (the router is float32 by the
# configuration, the taps are no matmul)
MATMUL_LEAVES = ("w_in", "w_out", "wq", "wk", "wv", "wo", "w1", "w3", "w2",
                 "e_gate", "e_up", "e_down")


def fp8_weights(params: dict) -> dict:
    """The first control: every matmul weight of the layers (the
    convolution's two projections, q, k, v, o, the dense SwiGLU, every
    expert) rounded to float8 (e4m3, one scale a matrix, an expert's
    each its own) and held in bfloat16 again — the step below the
    configuration's precision that a model whose step is weight reads
    tempts one to take.  The router, the taps, norms and the embedding
    (the head) stay as they were."""
    import jax
    import jax.numpy as jnp

    from benchmarks.planes.serve_latent_moe import round_e4m3

    def round_trip(w):  # [layers, ..., in, out], a layer at a time
        def one(wl):
            wl32 = wl.astype(jnp.float32)
            amax = jnp.max(jnp.abs(wl32), axis=(-2, -1), keepdims=True)
            scale = 448.0 / jnp.maximum(amax, 1e-30)
            return (round_e4m3(wl32 * scale) / scale).astype(wl.dtype)
        return jax.lax.map(one, w)

    fn = jax.jit(round_trip, donate_argnums=0)
    out = dict(params)
    for stack in ("conv", "attn", "dense", "moe"):
        out[stack] = {k: fn(v) if k in MATMUL_LEAVES else v
                      for k, v in params[stack].items()}
    return out


def zero_conv_state_at_admission() -> None:
    """The second control: an admitted row's convolution state is
    zeroed, every convolution layer's, so that decoding starts as if
    the prompt's last two tokens had never been seen by those layers.
    The check has to SEE the state: K and V, the prompt's own logits and
    the first token are as sound as ever.  Patches the program's prefill
    entry point IN THIS PROCESS; only `--control conv_state_zero` calls
    it."""
    from ray_tpu.models import lfm2

    sound = lfm2.forward

    def forward(cfg, params, tokens, conv=None, *, slots=None, **kw):
        logits, kv, conv = sound(cfg, params, tokens, conv, slots=slots, **kw)
        if conv is not None:
            conv = conv.at[:, slots].set(0, mode="drop")
        return logits, kv, conv

    lfm2.forward = forward


class BenchHybridService(base.BenchLlamaService):
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

        from benchmarks import weights_lfm2 as wts
        from ray_tpu.core.accelerators import device_report
        from ray_tpu.serve.llm_engine import LlamaEngine

        self._jax = jax
        self._compiles = _common.count_compiles()
        self.device = device_report()  # first touch of JAX
        timing = {"jax_start_s": time.perf_counter() - t0}
        m, e = cfg["model"], cfg["engine"]
        # the rehearsal runs in float32: its limits then tell a sound
        # program from either control at toy sizes too
        self._dtype = dtype = (jnp.float32 if opts.get("rehearse")
                               else jnp.bfloat16)
        self.mcfg = model_config(m, cfg["assumed"], dtype)
        params = wts.params(m, cfg["assumed"], self.seed, dtype)
        control = opts.get("control")
        if control == "fp8":
            params = fp8_weights(params)
        elif control == "conv_state_zero":
            zero_conv_state_at_admission()
        elif control:
            raise ValueError(f"this plane's controls are {CONTROLS}, not "
                             f"{control!r}")
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
        scopes' device time in the decode and in the prefill programs,
        and the tick ring's counters of both cache kinds and of the
        experts."""
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
                self._trace["dir"], SCOPES, ("jit_prefill_packed_",))
            keep = cmd.get("keep_trace_to")
            if keep:
                trace_reduce.copy_xplane(self._trace["dir"], keep)
        out["check"] = self._reference_check(int(cmd.get("sample", 8)))
        self._write(f"result_{self.rid}.json", out)

    # -- `correct`: the served tokens against the plain reference -------
    def _reference_check(self, sample: int) -> dict:
        """As the base class's: a seeded sample of this replica's own
        answers, teacher-forced through the float32 reference one layer
        at a time, each layer's weights made again from the seed.  So
        prefill and then decoding through BOTH caches is held to the
        reference's full forward pass, which has neither.  The sample
        goes through `group` sequences at a time: the model and both
        caches stay resident beside it."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks import weights_lfm2 as wts
        from benchmarks.reference import lfm2 as ref

        m, assumed, lim = (self.cfg["model"], self.cfg["assumed"],
                           self.cfg["reference"])
        served = list(self._served)
        if not served:
            return {"sampled": 0, "tokens": 0}
        rng = np.random.default_rng([self.seed, 0xC0DE])
        pick = rng.permutation(len(served))[:sample]
        span = int(lim["positions"])  # last <= span answers
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
        kw = ref.layer_kwargs(m, assumed)

        @functools.partial(jax.jit, donate_argnums=0)
        def one_layer(x, w):
            return jax.lax.map(lambda xb: ref.layer(xb, w, **kw), x)

        @jax.jit
        def tail(x, start, answer, ends):
            def one(args):
                xb, s, a = args
                lg = ref.head(jax.lax.dynamic_slice_in_dim(xb, s, span, 0),
                              ends["embedding_norm"], ends["tok_emb"],
                              m["norm_eps"])
                return ref.margins(lg, a), jnp.std(lg)
            return jax.lax.map(one, (x, start, answer))

        t0 = time.perf_counter()
        ends = wts.ends(m, assumed, self.seed, self._dtype)
        embed = jax.jit(lambda t, e: jax.vmap(
            lambda tt: ref.embed(tt, e))(t))
        margs, stds = [], []
        group = int(lim["group"])
        for lo in range(0, len(pick), group):
            x = embed(jnp.asarray(toks[lo:lo + group]), ends["tok_emb"])
            for l in range(m["num_hidden_layers"]):
                x = one_layer(x, wts.layer(m, assumed, self.seed, l,
                                           self._dtype))
            marg, lstd = tail(x, jnp.asarray(starts[lo:lo + group], jnp.int32),
                              jnp.asarray(answers[lo:lo + group]), ends)
            margs.append(np.asarray(marg))
            stds.append(np.asarray(lstd))
        marg = np.concatenate(margs)
        vals = np.concatenate([marg[r, o:o + c]
                               for r, (o, c) in enumerate(zip(offs, counts))])
        return {
            "sampled": int(len(pick)), "tokens": int(vals.size),
            "max_margin": float(vals.max()),
            "mean_margin": float(vals.mean()),
            "flipped_share": float((vals > 0).mean()),
            "logit_std": float(np.concatenate(stds).mean()),
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
    need = [os.path.join(REPO, "ray_tpu", "models", "lfm2.py")]
    missing = [p for p in need if not os.path.exists(p)]
    if missing:
        raise RuntimeError(
            f"this checkout cannot run {cell['name']}: it has no "
            f"{', '.join(os.path.relpath(p, REPO) for p in missing)}")
    lent = base.BenchLlamaService
    base.BenchLlamaService = BenchHybridService
    try:
        return base.run(cell, cfg, mix, args, t_process_start)
    finally:
        base.BenchLlamaService = lent
