"""The serve plane for a decoder whose context is mostly a RECURRENT
STATE (`ray_tpu.models.nemotron_h`: Mamba-2 layers on a per-slot float32
state and a convolution state, one attention layer in eleven on paged
folded pools, one chip's share of an expert layer that works in a latent
width), under a queue that mixes short and long prompts: the same path a
user's request takes as in `planes/serve.py` (`serve.run` -> HTTP proxy
-> router -> replica -> `LlamaEngine`), the same client, warm-up plan,
side channel, window and verdict, imported from there.  What differs is
what the replica builds and checks:

- the model's config and seeded weights (`weights_nemotron_h.py`), the
  engine with a prefill chunk (a prompt past it is admitted chunk by
  chunk, each chunk RESUMING from the slot's state);
- after the window it holds a sample of its own answers, over every
  prompt length of the mix (a packed admission, two chunks, four), WHOLE
  sequences, to the plain float32 reference (`reference/nemotron_h.py`:
  the recurrence one token at a time from zero), the engine's memory
  given back first;
- and what its SLOTS hold to that reference's state (`_probe_slots`,
  `verdict`'s two state rows): the tokens alone cannot see the state a
  chunk resumes from, which the seeded decays forget within a chunk;
- the four controls; the shape its paged kernel prints in a trace; the
  scopes' device time in the decode and in the prefill programs; the
  tick ring's counters of the state, the blocks and the held experts.

The context says `"plane": "serve"`: `cell.py` treats it as the serve
plane it is.
"""

from __future__ import annotations

import math
import os
import threading
import time

from benchmarks.manifest import REPO
from benchmarks.planes import _common
from benchmarks.planes import serve as base
from benchmarks.planes.serve_window_full import sample_answers

# the parts of a step the program marks with `jax.named_scope`; a name
# stack that holds two of them counts under the first listed (the
# router's sort lies inside `latent_moe_routed`'s call)
SCOPES = ("ssm_scan", "ssm_step", "ssm_conv", "ssm_proj", "moe_router",
          "latent_moe_routed", "latent_moe_proj", "moe_shared", "full_attn")
TICK_KEYS = ("seq", "t_wall", "admitted", "active", "queued", "live_tokens",
             "gather_blocks", "admit_s", "dispatch_s", "harvest_s",
             "row_steps", "row_steps_live", "state_rows_live")
MODEL_KEYS = ("experts_touched", "experts_total", "expert_load_max",
              "experts_held", "held_pairs", "ssm_bytes_live",
              "full_cache_tokens_live", "prefill_tokens",
              "state_chunks_resumed")
CONTROLS = ("fp8", "chunk_state_zero", "ssm_state_bf16", "prefill_state_zero")
NEED = (("models", "nemotron_h.py"), ("ops", "ssd.py"))


def kernel_predicates(cfg: dict) -> dict:
    """How the trace prints this model's Pallas kernels (told apart by
    what they return, as in `planes/serve.py`): the paged decode
    attention of the attention layer gives `[slots, heads, KV * 128]` (a
    token's heads lie side by side in one pool row, and the kernel
    returns the row's width); the append gives its pools back
    (aliased)."""
    m, e = cfg["model"], cfg["engine"]
    attn = (f"bf16[{e['slots']},{m['num_attention_heads']},"
            f"{m['num_key_value_heads'] * m['head_dim']}]")

    def is_kernel(n):
        return "custom-call(" in n and "tpu_custom_call" in n

    return {
        "paged_decode": lambda n: is_kernel(n) and n.split("=", 1)[1]
        .lstrip().startswith(attn),
        "paged_append": lambda n: is_kernel(n)
        and "output_to_operand_aliasing" in n,
    }


def verdict(ctx: dict, cfg: dict) -> dict:
    """`planes/serve.py`'s rows, and two of the SLOTS' leaves: how far
    the recurrent state and the convolution's inputs that the engine
    holds after a prompt's resumed chunks (and after decode steps behind
    them) lie from the reference's (`_probe_slots`).  No probe read, no
    verdict: the rows then read infinite."""
    out = base.verdict(ctx, cfg)
    lim = cfg["reference"]["state_probe"]
    read = [r["check"]["state"] for r in ctx["replicas"]
            if r["check"].get("state", {}).get("probes")]
    rows = out["rows"] + [
        (f"slot_{leaf}_state_rel_err_from_reference",
         max((s[f"{leaf}_rel_err"] for s in read), default=math.inf),
         lim[f"{leaf}_rel_err_limit"]) for leaf in ("ssm", "conv")]
    return {"rows": rows, "correct": all(v <= l for _, v, l in rows)}


def model_config(m: dict, dep: dict, dtype):
    from ray_tpu.models import nemotron_h

    assert m["model_type"] == "nemotron_h" and m["mlp_hidden_act"] == "relu2"
    assert m["mamba_hidden_act"] == "silu" and m["use_conv_bias"]
    assert not (m["mamba_proj_bias"] or m["attention_bias"] or m["mlp_bias"]
                or m["use_bias"] or m["tie_word_embeddings"])
    assert m["n_group"] == m["topk_group"] == 1 and m["norm_topk_prob"]
    assert m["n_shared_experts"] == 1 and not m["moe_shared_expert_overlap"]
    assert len(m["hybrid_override_pattern"]) == m["num_hidden_layers"]
    assert m["layer_norm_epsilon"] == m["norm_eps"]
    assert m["expand"] * m["hidden_size"] == (m["mamba_num_heads"]
                                              * m["mamba_head_dim"])
    return nemotron_h.NemotronHConfig(
        vocab_size=m["vocab_size"], max_seq_len=m["max_position_embeddings"],
        dim=m["hidden_size"], pattern=m["hybrid_override_pattern"],
        mamba_heads=m["mamba_num_heads"], mamba_head_dim=m["mamba_head_dim"],
        n_groups=m["n_groups"], state_size=m["ssm_state_size"],
        conv_kernel=m["conv_kernel"], scan_chunk=m["chunk_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        latent=m["moe_latent_size"],
        moe_intermediate=m["moe_intermediate_size"],
        shared_intermediate=m["moe_shared_expert_intermediate_size"],
        n_routed_experts=dep["router_experts"],
        experts_held=m["n_routed_experts"],
        expert_offset=dep["expert_offset"], top_k=m["num_experts_per_tok"],
        routed_scale=float(m["routed_scaling_factor"]),
        norm_eps=m["layer_norm_epsilon"], dtype=dtype)


def fp8_weights(params: dict) -> dict:
    """The control: every matmul weight of the layers (in_proj,
    out_proj, qkv, o, W_in, W_out, the shared and every held expert)
    rounded to float8 (e4m3, one scale a matrix, an expert's each its
    own) and held in bfloat16 again.  The router (float32 by the
    configuration), the convolution's taps, `dt_bias` / `A_log` / `D`,
    norms, embedding and head stay.  A leaf at a time and in its own
    buffer (donated): 9.3 GB of weights have no room for a second tree
    beside them."""
    import jax
    import jax.numpy as jnp

    from benchmarks.planes.serve_latent_moe import round_e4m3

    def round_trip(w):
        w32 = w.astype(jnp.float32)
        amax = jnp.max(jnp.abs(w32), axis=(-2, -1), keepdims=True)
        scale = 448.0 / jnp.maximum(amax, 1e-30)
        return (round_e4m3(w32 * scale) / scale).astype(w.dtype)

    rounded = jax.jit(round_trip, donate_argnums=0)
    for layer in params["layers"]:
        for k in [k for k, v in layer.items()
                  if v.ndim >= 2 and k not in ("router", "conv_w")]:
            layer[k] = rounded(layer.pop(k))
    return params


def start_chunks_from_zero() -> None:
    """The control `chunk_state_zero`: the state a prompt's second and
    later chunks START FROM is zeroed, the recurrent state and the
    convolution's alike (what an engine that did not carry the slot's
    leaves from chunk to chunk would compute).  Patches the program's
    two scans IN THIS PROCESS; only the control calls it."""
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    scan, conv = ssd.ssd_scan, ssd.conv_scan

    def zeroed(x):
        return None if x is None else jnp.zeros_like(x)

    ssd.ssd_scan = lambda *a, init=None, **kw: scan(
        *a, init=zeroed(init), **kw)
    ssd.conv_scan = lambda *a, prev=None, **kw: conv(
        *a, prev=zeroed(prev), **kw)


def _change_states(change, *writers) -> None:
    """`ops/ssd.py`'s `writers` (each returns `(y, states)`) hand back
    `change(states)` from now on, IN THIS PROCESS; only a control calls
    it."""
    from ray_tpu.ops import ssd

    def changed(fn):
        def wrapped(*a, **kw):
            y, state = fn(*a, **kw)
            return y, change(state)
        return wrapped

    for name in writers:
        setattr(ssd, name, changed(getattr(ssd, name)))


def leave_no_state() -> None:
    """The control `prefill_state_zero`: every state an ADMISSION program
    leaves is zero, the recurrent state and the convolution's alike, so
    the next chunk of a long prompt AND the first decode step start from
    nothing (`chunk_state_zero` and what lies past it: the served tokens
    begin 2,048 tokens behind the last chunk's start, where seeded
    decays have forgotten what that chunk started from, but right behind
    the state a prefill leaves)."""
    import jax.numpy as jnp

    _change_states(jnp.zeros_like, "ssd_scan", "conv_scan")


def hold_state_in_bf16() -> None:
    """The control `ssm_state_bf16`: the recurrent state rounded to
    bfloat16 WHEREVER IT IS WRITTEN, the states a prefill leaves and
    every decode step's (the leaf stays float32: the values are
    bfloat16's)."""
    import jax.numpy as jnp

    _change_states(lambda s: s.astype(jnp.bfloat16).astype(jnp.float32),
                   "ssd_scan", "ssd_step")


class BenchRecurrentService(base.BenchLlamaService):
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

        from benchmarks import weights_nemotron_h as wts
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
        self.mcfg = model_config(m, dep, dtype)
        params = wts.params(m, dep, self.seed, dtype=dtype, **self._std())
        if control == "fp8":
            params = fp8_weights(params)
        elif control == "chunk_state_zero":
            start_chunks_from_zero()
        elif control == "ssm_state_bf16":
            hold_state_in_bf16()
        elif control == "prefill_state_zero":
            leave_no_state()
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
        """`out_proj` is rescaled by the PUBLISHED depth
        (`assumed.rescale_prenorm_residual`)."""
        std = float(self.cfg["assumed"]["initializer_range"])
        return {"std": std, "out_std": std / float(
            self.cfg["published"]["num_hidden_layers"]) ** 0.5}

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
        chunked admission, the state resumed from chunk to chunk, and
        decoding through the blocks and both states, against a forward
        pass whose recurrence runs one token at a time from zero and
        has no cache.  Before that the slots' leaves are read
        (`_probe_slots`), then the engine's weights and cache are given
        back; the probes' sequences go through the reference beside the
        sample's, and `state` holds how far the slots lie from it."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks import weights_nemotron_h as wts
        from benchmarks.reference import nemotron_h as ref

        m, dep, lim = (self.cfg["model"], self.cfg["deployment"],
                       self.cfg["reference"])
        served = list(self._served)
        if not served:
            return {"sampled": 0, "tokens": 0}
        t0 = time.perf_counter()
        span = int(lim["positions"])  # the last <= span answers
        pick = sample_answers(served, sample, span, self.seed)
        # the engine is idle and has told what it had to tell: its slots
        # are read, then it gives its memory back
        probes = self._probe_slots([served[i][0] for i in pick],
                                   lim["state_probe"])
        self.engine.shutdown()
        self.engine.params = self.engine._cache = None
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

        def one_layer_keeping(l):
            """... and what a Mamba layer holds after `n` tokens."""
            kw = ref.layer_kwargs(m, l, offset=dep["expert_offset"])
            return jax.jit(lambda x, n, w: jax.lax.map(
                lambda a: ref.layer(a[0], w, qblock=min(128, x.shape[1]),
                                    keep=a[1], **kw), (x, n)),
                donate_argnums=0)

        @jax.jit
        def tail(x, start, answer, ends):
            def one(args):
                xb, s, a = args
                lg = ref.head(jax.lax.dynamic_slice_in_dim(xb, s, span, 0),
                              ends["final_norm"], ends["lm_head"],
                              m["layer_norm_epsilon"])
                return ref.margins(lg, a), jnp.std(lg)
            return jax.lax.map(one, (x, start, answer))

        std = self._std()
        ends = wts.ends(m, self.seed, self._dtype, std["std"])
        embed = jax.jit(lambda t, e: jax.vmap(
            lambda tt: ref.embed(tt, e))(t))
        x = embed(jnp.asarray(toks), ends["tok_emb"])
        # the probes' sequences beside the sample's, a layer's weights
        # made once for both
        if probes:
            count = [len(p["tokens"]) for p in probes]
            seen = np.zeros((len(probes), base._cdiv(max(count), 128) * 128),
                            np.int32)
            for r, p in enumerate(probes):
                seen[r, :count[r]] = p["tokens"]
            xp = embed(jnp.asarray(seen), ends["tok_emb"])
            count = jnp.asarray(count, jnp.int32)
        for l in range(m["num_hidden_layers"]):
            w = wts.layer(m, dep, self.seed, l, self._dtype, **std)
            x = one_layer(l)(x, w)
            if probes:
                xp, held = one_layer_keeping(l)(xp, count, w)
                _state_errors(probes, held)
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
            "state": {
                "probes": [{k: v for k, v in p.items()
                            if k not in ("tokens", "ssm", "conv")}
                           for p in probes],
                **{f"{leaf}_rel_err": max(
                    (max(p[f"{leaf}_rel_err"]) for p in probes),
                    default=math.inf) for leaf in ("ssm", "conv")}},
            "seconds": time.perf_counter() - t0,
        }

    def _probe_slots(self, prompts: list, spec: dict) -> list:
        """What the engine's SLOTS hold, for `verdict`'s state rows.  One
        sampled prompt of each length past a prefill chunk is cut `tail`
        tokens into its LAST chunk and sent through the timed engine
        again (idle now: the same programs, the same carry), once for
        each count of `answers`; when its answer is back the slot's
        leaves still hold what the request left (a dead row's state
        stays as it was), and are copied to the host.  With an answer of
        one token no decode step ran: the leaves are what the prompt's
        chunks left, each chunk but the first RESUMED from the slot;
        with `n`, `n - 1` decode steps followed.  `tail` is short on
        purpose: the seeded decays forget within hundreds of tokens, so
        a state read a whole chunk behind a boundary cannot tell what
        the chunk started from, and one read `tail` tokens behind it
        can.  Returns, a probe, the tokens whose state the slot holds
        and the Mamba layers' leaves of that slot."""
        import numpy as np

        eng, step = self.engine, self.cfg["engine"]["prefill_chunk"]
        at = {leaf.name: i for i, leaf in enumerate(eng._pool.spec)}
        by_len = {len(p): p for p in reversed(prompts) if len(p) > step}
        probes = []
        for T, prompt in sorted(by_len.items()):
            cut = (T - 1) // step * step + int(spec["tail"])
            for n in spec["answers"]:
                slot = eng._free[-1]  # the idle engine's next slot
                resumed = eng.stats()["state_chunks_resumed"]
                answer = eng.submit(list(prompt[:cut]), int(n)).result(
                    timeout=600)
                resumed = eng.stats()["state_chunks_resumed"] - resumed
                if resumed != cut // step:
                    raise RuntimeError(
                        f"a probe of {cut} tokens resumed {resumed} chunks, "
                        f"not {cut // step}")
                probes.append({
                    "prompt": cut, "chunks_resumed": resumed,
                    "answer": len(answer),
                    # the last token of the answer was fed to no step
                    "tokens": list(prompt[:cut]) + list(answer)[:-1],
                    "ssm": np.asarray(eng._cache[at["ssm"]][:, slot]),
                    "conv": np.asarray(eng._cache[at["conv"]][:, slot],
                                       np.float32),
                    "ssm_rel_err": [], "conv_rel_err": []})
        return probes


def _state_errors(probes: list, held) -> None:
    """A Mamba layer's reference states `held` (`None` for another kind
    of layer) against the probes' slots: appends, a probe, the recurrent
    state's distance from the reference's over the reference's norm, a
    HEAD at a time and averaged over the heads (the heads whose decay
    is slow hold small states and carry the long context: one norm over
    the layer would weigh them least), and the convolution's inputs'
    over the layer."""
    import numpy as np

    if held is None:
        return

    def norm(a):  # [heads, head_dim, state] -> [heads]
        return np.sqrt((a.astype(np.float64) ** 2).sum((-2, -1)))

    l = len(probes[0]["ssm_rel_err"])  # the Mamba layer's place in the leaves
    for p, ssm, conv in zip(probes, np.asarray(held["ssm"]),
                            np.asarray(held["conv"])):
        p["ssm_rel_err"].append(float(np.mean(
            norm(p["ssm"][l] - ssm) / np.maximum(norm(ssm), 1e-30))))
        conv = conv.reshape(-1)
        p["conv_rel_err"].append(float(
            np.linalg.norm(p["conv"][l] - conv) / np.linalg.norm(conv)))


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
    base.BenchLlamaService = BenchRecurrentService
    try:
        return base.run(cell, cfg, mix, args, t_process_start)
    finally:
        base.BenchLlamaService = lent
