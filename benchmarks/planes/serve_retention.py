"""The serve plane for a power-retention decoder
(`ray_tpu.models.brumby`): the same path a user's request takes as in
`planes/serve.py` (`serve.run` -> HTTP proxy -> router -> replica ->
`LlamaEngine`), the same client, warm-up plan, side channel, window and
verdict, all imported from there, as `planes/serve_latent_moe.py` does.
What differs is what the replica builds and checks: the model's config
and seeded weights (`weights_brumby.py`), the plain reference its
answers are held to (`reference/brumby.py`, the quadratic form), the
controls, the shapes its kernels print in a trace, and the per-slot
cache's counters it ships from the tick ring.

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
SCOPES = ("retention_attn", "dense_mlp")
TICK_KEYS = ("seq", "admitted", "active", "queued", "live_tokens",
             "gather_blocks", "admit_s", "dispatch_s", "harvest_s")
STATE_KEYS = ("t_wall", "state_rows_live", "state_rows_flushed",
              "row_steps_live", "row_steps")
CONTROLS = ("fp8", "state_bf16")


def kernel_predicates(cfg: dict) -> dict:
    """How the trace prints this model's Pallas kernels (told apart by
    what they return, as in `planes/serve.py`): the step that WRITES
    the state (a chunk's flush) gives the numerators first, `f32[slots,
    kv_heads, head_dim, head_dim]` (also under the label
    `paged_decode`: one call a layer and chunk program, so that
    `decode_step_ms` finds the decode programs of this cell as it
    stands); the step that only READS it gives the denominators first,
    `f32[slots, kv_heads, 8, head_dim]` (the group's query heads padded
    to a register's 8 rows); the chunked prefill's first result is
    `bf16[kv_heads, group, N, head_dim]`."""
    m, e = cfg["model"], cfg["engine"]
    KV, d = m["num_key_value_heads"], m["head_dim"]
    group = m["num_attention_heads"] // KV
    decode = f"(f32[{e['slots']},{KV},{d},{d}]"
    read = f"(f32[{e['slots']},{KV},{-(-group // 8) * 8},{d}]"
    prefill = f"(bf16[{KV},{group},"

    def is_kernel(n):
        return "custom-call(" in n and "tpu_custom_call" in n

    def gives(n, shape):
        return is_kernel(n) and n.split("=", 1)[1].lstrip().startswith(shape)

    return {
        "paged_decode": lambda n: gives(n, decode),
        "retention_decode": lambda n: gives(n, decode),
        "retention_read": lambda n: gives(n, read),
        "retention_prefill": lambda n: gives(n, prefill),
    }


def model_config(m: dict, assumed: dict, dtype):
    from ray_tpu.models import brumby

    assert m["model_type"] == "brumby" and not m["attention_bias"]
    assert m["rope_scaling"] is None and not m["tie_word_embeddings"]
    assert assumed["degree"] == 2 and m["hidden_act"] == "silu"
    return brumby.BrumbyConfig(
        vocab_size=m["vocab_size"], max_seq_len=m["max_position_embeddings"],
        dim=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        intermediate=m["intermediate_size"],
        rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
        retention_eps=float(assumed["retention_eps"]), dtype=dtype)


def fp8_weights(params: dict) -> dict:
    """The control: every matmul weight of the layers (q, k, v, o and
    the SwiGLU's three) rounded to float8 (e4m3, one scale a matrix) and
    held in bfloat16 again: the step below the configuration's
    precision.  The gate (float32 by the configuration), norms,
    embedding and head stay as they were."""
    import jax
    import jax.numpy as jnp

    from benchmarks.planes.serve_latent_moe import round_e4m3

    def round_trip(w):  # [layers, in, out], a layer at a time
        def one(wl):
            wl32 = wl.astype(jnp.float32)
            scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(wl32)), 1e-30)
            return (round_e4m3(wl32 * scale) / scale).astype(wl.dtype)
        return jax.lax.map(one, w)

    fn = jax.jit(round_trip, donate_argnums=0)
    out = dict(params)
    out["blocks"] = {k: fn(v) if v.ndim == 3 and k != "wg" else v
                     for k, v in params["blocks"].items()}
    return out


def round_bf16(x):
    """float32 -> the nearest bfloat16 (ties to even), in integer
    arithmetic on the bits and not as a cast: a cast pair inside one
    program came back bit-identical on the v5e (`round_e4m3`'s note)."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def hold_state_in_bf16() -> None:
    """The second control: the state and the key sum rounded to
    bfloat16 wherever a kernel leaves them (a layer's slots after every
    prefill and every flush, which is once a decode chunk where the
    program writes once a chunk), and what the reading steps take in
    beside them, the chunk's held log-gates, rounded as well: the state
    they read is rounded already, nothing else writes it.  Patches the
    program's three entry points IN THIS PROCESS; only `--control
    state_bf16` calls it."""
    import jax

    from ray_tpu.ops import retention as ret

    def rounded(fn, layer_at):
        def wrapped(*args, **kw):
            o, state, keysum = fn(*args, **kw)
            layer = args[layer_at]

            def one(leaf):
                rows = jax.lax.dynamic_index_in_dim(leaf, layer, 0, True)
                return jax.lax.dynamic_update_index_in_dim(
                    leaf, round_bf16(rows), layer, 0)
            return o, one(state), one(keysum)
        return wrapped

    def takes_rounded(fn):
        def wrapped(q, k, v, g, state, keysum, pending, *rest, **kw):
            return fn(q, k, v, g, state, keysum,
                      pending._replace(G=round_bf16(pending.G)), *rest, **kw)
        return wrapped

    # `layer` is the 8th positional argument of the one, the 10th of
    # the other (`models/brumby.py` passes it so)
    ret.retention_decode = rounded(ret.retention_decode, 7)
    ret.retention_prefill = rounded(ret.retention_prefill, 9)
    ret.retention_read = takes_rounded(ret.retention_read)


class BenchRetentionService(base.BenchLlamaService):
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

        from benchmarks import weights_brumby as wts
        from ray_tpu.core.accelerators import device_report
        from ray_tpu.serve.llm_engine import LlamaEngine

        self._jax = jax
        self._compiles = _common.count_compiles()
        self.device = device_report()  # first touch of JAX
        timing = {"jax_start_s": time.perf_counter() - t0}
        m, e = cfg["model"], cfg["engine"]
        self.mcfg = model_config(m, cfg["assumed"], jnp.bfloat16)
        params = wts.params(m, cfg["assumed"], self.seed)
        control = opts.get("control")
        if control == "fp8":
            params = fp8_weights(params)
        elif control == "state_bf16":
            hold_state_in_bf16()
        elif control:
            raise ValueError(f"this plane's controls are {CONTROLS}, not "
                             f"{control!r}")
        jax.block_until_ready(params)
        timing["weights_s"] = time.perf_counter() - t0
        self.engine = LlamaEngine(
            self.mcfg, params, slots=e["slots"], max_len=e["max_len"],
            chunk=e["chunk"], block_size=e["block_size"],
            prefix_cache=e["prefix_cache"])
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
        scopes' device time, and the tick ring's live-row counters."""
        out = {"rid": self.rid, "device": dict(self.device),
               "served": len(self._served)}
        t = getattr(self, "_trace_thread", None)
        if t is not None:
            t.join(timeout=120)
        stats = self.engine.stats()
        out["engine"] = {k: v for k, v in stats.items() if k != "tick_ring"}
        out["tick_ring"] = [
            {**{k: r[k] for k in TICK_KEYS},
             **{k: r[k] for k in STATE_KEYS if k in r}}
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
        at a time, each layer's weights made again from the seed.  The
        sample goes through `group` sequences at a time and the head
        `vocab_block` columns at a time: the state cache stays resident
        beside it."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks import weights_brumby as wts
        from benchmarks.reference import brumby as ref

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
                return ref.head_margins(
                    jax.lax.dynamic_slice_in_dim(xb, s, span, 0),
                    ends["final_norm"], ends["lm_head"], m["rms_norm_eps"],
                    a, int(lim["vocab_block"]))
            return jax.lax.map(one, (x, start, answer))

        t0 = time.perf_counter()
        # `group` sequences at a time through embedding, layers and
        # head, each made from the seed when its turn comes and let go:
        # the embedding and the head are 1.56 GB each in bfloat16, a
        # layer 0.66 GB, and the model and its states stay resident
        embed = jax.jit(lambda t, e: jax.vmap(
            lambda tt: ref.embed(tt, e))(t))
        margs, stds = [], []
        group = int(lim["group"])
        for lo in range(0, len(pick), group):
            x = embed(jnp.asarray(toks[lo:lo + group]),
                      wts.tok_emb(m, assumed, self.seed))
            for l in range(m["num_hidden_layers"]):
                x = one_layer(x, wts.layer(m, assumed, self.seed, l))
            marg, lstd = tail(x, jnp.asarray(starts[lo:lo + group], jnp.int32),
                              jnp.asarray(answers[lo:lo + group]),
                              wts.head(m, assumed, self.seed))
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
    need = [os.path.join(REPO, "ray_tpu", *p) for p in (
        ("models", "brumby.py"), ("ops", "retention.py"))]
    missing = [p for p in need if not os.path.exists(p)]
    if missing:
        raise RuntimeError(
            f"this checkout cannot run {cell['name']}: it has no "
            f"{', '.join(os.path.relpath(p, REPO) for p in missing)}")
    lent = base.BenchLlamaService
    base.BenchLlamaService = BenchRetentionService
    try:
        return base.run(cell, cfg, mix, args, t_process_start)
    finally:
        base.BenchLlamaService = lent
