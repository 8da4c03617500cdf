"""LLM serving example: a Llama replica behind serve (BASELINE #5).

Reference capability: "Ray Serve Llama-3 8B JAX replica (autoscaled TPU
deployment)" — a deployment hosting a jax Llama with KV-cached decoding
(`models/llama.py` prefill/decode_step/generate), dynamic request
batching (`@serve.batch` — batches compile once per shape and reuse the
program, the TPU-native win), and serve autoscaling from queue metrics.

Token-id interface (no tokenizer dependency in-image): POST
`{"tokens": [[1,2,3,...]], "max_new_tokens": 16}` -> generated ids.

    from ray_tpu.examples.serve_llm import run
    handle = run(model_size="tiny")          # or "llama2_7b"/"llama3_8b"
    out = handle.generate.remote([[1, 2, 3]]).result()

A replica OWNS one chip: both deployments ask the scheduler for
`num_tpus=1`, and that lease is what exposes the chip to the replica's
worker process (`core/accelerators.py`).  `jax_platform="cpu"` is the
explicit way off the chip — no lease is requested and the replica runs
on the CPU its worker was spawned with (the tests and the verify recipe
pass it).
"""

from __future__ import annotations

import json
from collections import defaultdict
from typing import List, Optional

from ray_tpu import serve

MODEL_SIZES = ("tiny", "llama1b4", "llama2_7b", "llama3_8b")


def _model_config(model_size: str):
    """The size table both deployments (and the AOT compile tests)
    share."""
    from ray_tpu.models import llama

    if model_size not in MODEL_SIZES:
        raise ValueError(f"model_size must be one of {MODEL_SIZES}")
    return {
        "tiny": llama.LlamaConfig.tiny,
        # the per-chip serving unit for a 16 GB v5e-1 (same 1.4B
        # class as the llama_lora train bench); bigger models shard
        # over a mesh, the replica stays the per-host unit
        "llama1b4": lambda: llama.LlamaConfig(
            vocab_size=32000, max_seq_len=1024, dim=2048, n_layers=22,
            n_heads=16, n_kv_heads=16, intermediate=5632,
        ),
        "llama2_7b": llama.LlamaConfig.llama2_7b,
        "llama3_8b": llama.LlamaConfig.llama3_8b,
    }[model_size]()


def _build_model(model_size: str, seed: int):
    """Shared (cfg, params) constructor for both deployments: one
    place owns the bf16 serving cast."""
    import jax

    from ray_tpu.models import llama

    cfg = _model_config(model_size)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    if model_size != "tiny":
        # serving decode is weight-read bound: bf16 weights halve
        # HBM footprint and double effective decode bandwidth
        import jax.numpy as jnp

        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    return cfg, params


def _bench_generate(cfg, params, batch: int, prompt_len: int,
                    max_new_tokens: int, iters: int) -> dict:
    """Bare `llama.generate` timing in the calling process — the
    no-serve baseline both deployments' bench_direct expose; one body
    so the overhead metric can never desynchronize between them."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.core.accelerators import device_report
    from ray_tpu.models import llama

    prompt = jax.random.randint(
        jax.random.PRNGKey(0), (batch, prompt_len), 0,
        cfg.vocab_size, dtype=jnp.int32,
    )
    np.asarray(llama.generate(
        cfg, params, prompt, max_new_tokens
    ))  # warmup: compiles prefill + decode; host read = sync
    t0 = time.perf_counter()
    for _ in range(iters):
        np.asarray(llama.generate(cfg, params, prompt, max_new_tokens))
    dt = time.perf_counter() - t0
    return {
        "tokens_per_sec": batch * max_new_tokens * iters / dt,
        "seconds_per_iter": dt / iters,
        "batch": batch,
        # where this ran, from the process that owns the device: a
        # launcher reads it here instead of probing a backend itself
        "device": device_report(),
    }

class _ChipDeployment(serve.Deployment):
    """A deployment whose replica owns one chip: `bind()` adds
    `num_tpus=1` to the replica actor's demand unless the app says
    `jax_platform="cpu"` (or already names its own TPU demand)."""

    def bind(self, *args, **kwargs):
        app = super().bind(*args, **kwargs)
        asks = any(k in self.resources for k in ("num_tpus", "TPU"))
        if kwargs.get("jax_platform") != "cpu" and not asks:
            app.deployment = serve.Deployment(
                self.func_or_class, self.name, self.config,
                {**self.resources, "num_tpus": 1},
            )
        return app


def _chip_deployment(**options):
    def wrap(cls):
        d = serve.deployment(**options)(cls)
        return _ChipDeployment(d.func_or_class, d.name, d.config,
                               d.resources)

    return wrap


def _pin_platform(jax_platform: Optional[str]) -> None:
    """`jax_platform` given: this replica runs there whatever its
    worker was spawned with (before any array op touches a backend)."""
    if jax_platform:
        import jax

        jax.config.update("jax_platforms", jax_platform)


@_chip_deployment(
    max_ongoing_requests=32,
    autoscaling_config={"min_replicas": 1, "max_replicas": 2,
                        "target_ongoing_requests": 16},
)
class LlamaService:
    """One replica = one model instance on this host's chips.

    Scaling out is serve autoscaling (more replicas); scaling up is a
    mesh passed to the model (tp/sp sharding rules) — the single-replica
    path here keeps the example self-contained.
    """

    def __init__(self, model_size: str = "tiny", max_new_tokens: int = 16,
                 seed: int = 0, max_batch_size: int = 8,
                 bucket_fill_timeout_s: Optional[float] = None,
                 jax_platform: Optional[str] = None):
        _pin_platform(jax_platform)

        from ray_tpu.models import llama

        self._llama = llama
        self.cfg, self.params = _build_model(model_size, seed)
        self.max_new_tokens = max_new_tokens
        # request clamp: each pow-2 generation-length bucket is its own
        # compiled program AND its own KV-cache footprint, so the
        # configured default is also the per-request ceiling (pass a
        # larger max_new_tokens at deploy time to allow longer asks)
        self.max_new_tokens_limit = max_new_tokens
        self._max_batch_size = max_batch_size
        # instance-level batching config consumed by @serve.batch.
        # bucket_fill_timeout_s (opt-in): once a gathering batch sits
        # at an upper pow-2 boundary, flush after this wait instead of
        # letting stragglers re-pad it into the next bucket (the
        # serialized 32+16 ragged pair that capped max_batch at 16 in
        # PERF.md's serve sweep)
        self.__serve_batch_overrides__ = {
            "_generate_batch": {
                "max_batch_size": max_batch_size,
                "bucket_fill_timeout_s": bucket_fill_timeout_s,
            },
        }

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.02)
    async def _generate_batch(self, requests: List[dict]) -> List[List[int]]:
        """Batched generation.  Prompts are grouped by length so each
        group is one [B, T] generate call — XLA compiles per shape, and
        same-shape batches reuse the compiled prefill/decode programs.
        Each group is padded up to the next power-of-two batch size
        (repeating the first row) so only log2(max_batch)+1 shapes ever
        compile, whatever sizes the batcher hands us — shape-bucketing,
        the standard XLA serving trick (a fresh [G, T] shape is a
        multi-second compile; a bucketed one is a cache hit)."""
        import asyncio

        import jax.numpy as jnp
        import numpy as np

        def _run_groups():
            out: List[Optional[List[int]]] = [None] * len(requests)
            groups = defaultdict(list)
            for i, req in enumerate(requests):
                groups[(len(req["tokens"]), req["max_new_tokens"])].append(i)
            for (T, n_new), idxs in groups.items():
                arr = jnp.asarray(
                    [requests[i]["tokens"] for i in idxs], jnp.int32
                )
                G = arr.shape[0]
                # next pow2 >= G, but never beyond the configured batch
                # cap the replica was memory-sized for
                bucket = min(1 << (G - 1).bit_length(),
                             self._max_batch_size)
                if bucket > G:
                    arr = jnp.concatenate(
                        [arr, jnp.broadcast_to(arr[:1], (bucket - G, T))]
                    )
                # generation length is a compile axis too (the fused
                # program scans n_new steps): bucket it to the next
                # pow2 and slice, so a client sweeping max_new_tokens
                # cannot force a compile per distinct value; the KV
                # cache is (T + n) slots, so never run past max_seq_len
                # (generate() clamps per request, so this stays >= 1)
                n_bucket = max(1, min(1 << max(0, n_new - 1).bit_length(),
                                      self.cfg.max_seq_len - T))
                gen = self._llama.generate(
                    self.cfg, self.params, arr, n_bucket, temperature=0.0
                )
                # ONE device->host transfer for the whole batch:
                # element-wise int() on the device array is a
                # per-TOKEN host read, each a full round trip
                gen_host = np.asarray(gen)
                for j, i in enumerate(idxs):
                    out[i] = [int(t) for t in gen_host[j, :n_new]]
            return out

        # the decode loop blocks (per-token device syncs): run it on
        # the worker pool so the replica's event loop keeps gathering
        # batches and serving health checks
        from ray_tpu.core.runtime import get_runtime

        return await asyncio.get_running_loop().run_in_executor(
            get_runtime()._exec_pool, _run_groups
        )

    async def generate(self, token_lists: List[List[int]],
                       max_new_tokens: Optional[int] = None) -> List[List[int]]:
        """Python-handle surface: a list of prompts (token ids)."""
        import asyncio

        n_new = (max_new_tokens if max_new_tokens is not None
                 else self.max_new_tokens)
        n_new = max(1, min(int(n_new), self.max_new_tokens_limit))
        # per-request validation/clamping BEFORE batching: a bad
        # request must fail alone, never take its co-batched group
        # down with it, and the clamped length must drive the grouping
        # (so n_bucket below is always >= 1)
        limit = self.cfg.max_seq_len
        reqs = []
        for toks in token_lists:
            if not toks or len(toks) >= limit:
                raise ValueError(
                    f"prompt length must be in [1, {limit - 1}] "
                    f"(got {len(toks)}; max_seq_len={limit})"
                )
            reqs.append({"tokens": toks,
                         "max_new_tokens": min(n_new, limit - len(toks))})
        return list(await asyncio.gather(*[
            self._generate_batch(r) for r in reqs
        ]))

    def bench_direct(self, batch: int, prompt_len: int,
                     max_new_tokens: int, iters: int = 3) -> dict:
        """Bare `llama.generate` baseline in the replica process (the
        chip owner); shared body with the continuous deployment."""
        return _bench_generate(self.cfg, self.params, batch,
                               prompt_len, max_new_tokens, iters)

    async def __call__(self, request):
        body = request.json() if request.body() else {}
        tokens = body["tokens"]
        n_new = int(body.get("max_new_tokens", self.max_new_tokens))
        result = await self.generate(tokens, n_new)
        return {"tokens": result}


@_chip_deployment(
    max_ongoing_requests=256,
)
class ContinuousLlamaService:
    """Continuous-batching variant (reference capability: the
    vLLM-on-Ray serving pattern): requests join a RESIDENT decode
    batch mid-flight via `serve.llm_engine.LlamaEngine` instead of
    gather-batching whole generations — the decode batch stays full,
    so weight reads amortize over every active sequence.  Measured
    nearly 2x the gather-batched throughput at the same shapes
    (PERF.md round 5).  The engine's KV cache is PAGED (block pool +
    radix prefix cache), so `max_len` only caps one sequence — an
    over-provisioned pool costs HBM, not per-step time — and requests
    sharing a prompt prefix (system prompts) skip its prefill."""

    def __init__(self, model_size: str = "tiny", max_new_tokens: int = 16,
                 seed: int = 0, slots: int = 32, chunk: int = 8,
                 max_len: Optional[int] = None, block_size: int = 16,
                 kv_blocks: Optional[int] = None, prefix_cache: bool = True,
                 max_queued: Optional[int] = None,
                 decode_kernel: str = "auto", kv_dtype: str = "model",
                 weight_dtype: str = "model",
                 engine_config: Optional[dict] = None,
                 jax_platform: Optional[str] = None):
        _pin_platform(jax_platform)

        from ray_tpu.serve.config import LLMEngineConfig
        from ray_tpu.serve.llm_engine import LlamaEngine

        if engine_config is not None:
            # declarative form (deploy documents / user_config): one
            # validated dict replaces the flat kwargs wholesale
            from ray_tpu.serve.schema import LLMEngineSchema

            ecfg = LLMEngineSchema.model_validate(engine_config).to_config()
        else:
            ecfg = LLMEngineConfig(
                slots=slots, chunk=chunk, max_len=max_len,
                block_size=block_size, kv_blocks=kv_blocks,
                prefix_cache=prefix_cache, max_queued=max_queued,
                decode_kernel=decode_kernel, kv_dtype=kv_dtype,
                weight_dtype=weight_dtype,
            ).validate()

        cfg, params = _build_model(model_size, seed)
        if ecfg.weight_dtype == "int8":
            from ray_tpu.models import llama as _llama

            params = _llama.quantize_weights_int8(params)
        # max_queued mirrors the deployment's max_queued_requests at
        # the ENGINE queue (the replica callable can't see its
        # DeploymentConfig): overflow submissions fail immediately
        # with BackPressureError -> HTTP 503 + Retry-After
        self.engine = LlamaEngine(cfg, params, **ecfg.engine_kwargs())
        self.max_new_tokens = max_new_tokens
        self.max_new_tokens_limit = max_new_tokens

    async def generate(self, token_lists, max_new_tokens=None):
        import asyncio

        from ray_tpu.core.runtime import remaining_deadline_s

        n_new = (max_new_tokens if max_new_tokens is not None
                 else self.max_new_tokens)
        n_new = max(1, min(int(n_new), self.max_new_tokens_limit))
        # the caller's end-to-end budget (handle.options(timeout_s=...)
        # propagated into this task gRPC-style) rides into the engine
        # queue, so a request that cannot decode its first token before
        # the caller gives up is SHED before it burns a prefill
        budget = remaining_deadline_s()
        futs = [
            asyncio.wrap_future(
                self.engine.submit(list(t), n_new, timeout_s=budget)
            )
            for t in token_lists
        ]
        return list(await asyncio.gather(*futs))

    async def __call__(self, request):
        body = request.json() if request.body() else {}
        n_new = int(body.get("max_new_tokens", self.max_new_tokens))
        return {"tokens": await self.generate(body["tokens"], n_new)}

    def stats(self):
        """Queue-depth/TTFT/occupancy signals, piggybacked by the serve
        replica onto health checks: the controller feeds `queue_depth`
        into routing tables (queue-depth-aware pow-2 across replicas)
        and the rest into /api/serve."""
        return self.engine.stats()

    def bench_direct(self, batch: int, prompt_len: int,
                     max_new_tokens: int, iters: int = 3) -> dict:
        """Bare gather-generate baseline in the engine's process (the
        engine idles between requests, so the chip is free); shared
        body with LlamaService."""
        return _bench_generate(self.engine.cfg, self.engine.params,
                               batch, prompt_len, max_new_tokens, iters)

    def reference_check(self, token_lists, generated) -> List[dict]:
        """The engine's answers held to the plain model, inside the
        replica (the chip owner; the engine idles between requests).
        For each prompt and the tokens the engine `generated` for it:
        `reference` — greedy `llama.generate` on the prompt (what the
        CPU tests compare bit-exactly); `margins` — teacher-forced
        through one dense `llama.forward` over prompt + generated, how
        far each generated token's logit sits below that position's
        argmax (0.0 = it IS the argmax).  On the chip the kernel route
        and the dense cache route round bf16 in different orders, so
        at random weights a near-tie can flip a token; a flipped token
        still has a margin of rounding size, a wrong one does not."""
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import llama

        cfg, params = self.engine.cfg, self.engine.params
        out = []
        for prompt, gen in zip(token_lists, generated):
            ref = np.asarray(llama.generate(
                cfg, params, jnp.asarray([prompt], jnp.int32), len(gen)
            ))[0]
            logits = np.asarray(llama.forward(
                cfg, params,
                jnp.asarray([list(prompt) + list(gen[:-1])], jnp.int32),
            ))[0, len(prompt) - 1:]
            picked = logits[np.arange(len(gen)), np.asarray(gen)]
            out.append({
                "reference": [int(t) for t in ref],
                "margins": [float(m) for m in logits.max(-1) - picked],
                "logit_std": float(logits.std()),
            })
        return out

    def __serve_drain__(self):
        """Graceful scale-down hook (called by the replica once the
        controller has removed it from routing tables): stop admitting
        new requests while live sequences decode to completion."""
        self.engine.begin_drain()

    def __serve_shutdown__(self):
        """Post-drain hook: release the KV block pool deterministically
        instead of relying on actor-kill teardown."""
        self.engine.shutdown()

    def __del__(self):
        try:
            self.engine.shutdown()
        except Exception:
            pass


def build_app(model_size: str = "tiny", max_new_tokens: int = 16,
              jax_platform: Optional[str] = None):
    return LlamaService.bind(model_size=model_size,
                             max_new_tokens=max_new_tokens,
                             jax_platform=jax_platform)


def run(model_size: str = "tiny", max_new_tokens: int = 16,
        name: str = "llm", route_prefix: str = "/llm",
        timeout_s: float = 300.0, jax_platform: Optional[str] = None):
    """Deploy and return the app handle.  The ready timeout covers a
    cold replica init on real chips (first jax/TPU init in a fresh
    worker is tens of seconds; big-model weight init longer)."""
    return serve.run(
        build_app(model_size, max_new_tokens, jax_platform),
        name=name, route_prefix=route_prefix, timeout_s=timeout_s,
    )
