"""LLM serving example: a Llama replica behind serve (BASELINE #5).

Reference capability: "Ray Serve Llama-3 8B JAX replica (autoscaled TPU
deployment)" — a deployment hosting a jax Llama behind the
continuous-batching engine (`serve.llm_engine.LlamaEngine`: a resident
decode batch over a paged KV cache that requests join mid-flight).

Token-id interface (no tokenizer dependency in-image): POST
`{"tokens": [[1,2,3,...]], "max_new_tokens": 16}` -> generated ids.  A
model that generates by diffusion over blocks (`model_size="sdar_tiny"`)
also takes `"denoising_steps"` and `"confidence_threshold"` in the body,
a request's own, and answers `"forwards"` beside the tokens.

    from ray_tpu.examples.serve_llm import run
    handle = run(model_size="tiny")          # or "llama2_7b"/"llama3_8b"
    out = handle.generate.remote([[1, 2, 3]]).result()

A replica OWNS one chip: the deployment asks the scheduler for
`num_tpus=1`, and that lease is what exposes the chip to the replica's
worker process (`core/accelerators.py`).  `jax_platform="cpu"` is the
explicit way off the chip — no lease is requested and the replica runs
on the CPU its worker was spawned with (the tests and the verify recipe
pass it).
"""

from __future__ import annotations

from typing import List, Optional

from ray_tpu import serve

MODEL_SIZES = ("tiny", "llama1b4", "llama2_7b", "llama3_8b", "sdar_tiny")


def _model_config(model_size: str):
    """The size table the deployment and the AOT compile tests
    share."""
    from ray_tpu.models import llama

    if model_size not in MODEL_SIZES:
        raise ValueError(f"model_size must be one of {MODEL_SIZES}")
    if model_size == "sdar_tiny":
        from ray_tpu.models import sdar

        return sdar.SdarMoeConfig.tiny()
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
    """The (cfg, params) constructor: one place owns the bf16 serving
    cast."""
    import jax

    from ray_tpu.models import llama

    cfg = _model_config(model_size)
    if model_size == "sdar_tiny":
        from ray_tpu.models import sdar

        return cfg, sdar.init_params(cfg, jax.random.PRNGKey(seed), std=0.2)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    if model_size != "tiny":
        # serving decode is weight-read bound: bf16 weights halve
        # HBM footprint and double effective decode bandwidth
        import jax.numpy as jnp

        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    return cfg, params


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


@_chip_deployment(
    max_ongoing_requests=256,
)
class ContinuousLlamaService:
    """Continuous batching (reference capability: the vLLM-on-Ray
    serving pattern): requests join a RESIDENT decode batch mid-flight
    via `serve.llm_engine.LlamaEngine` instead of gather-batching whole
    generations — the decode batch stays full, so weight reads amortize
    over every active sequence.  The engine's KV cache is PAGED (block
    pool + radix prefix cache), so `max_len` only caps one sequence —
    an over-provisioned pool costs HBM, not per-step time — and
    requests sharing a prompt prefix (system prompts) skip its
    prefill."""

    def __init__(self, model_size: str = "tiny", max_new_tokens: int = 16,
                 seed: int = 0, slots: int = 32, chunk: int = 8,
                 max_len: Optional[int] = None, block_size: int = 16,
                 kv_blocks: Optional[int] = None, prefix_cache: bool = True,
                 max_queued: Optional[int] = None,
                 decode_kernel: str = "auto", kv_dtype: str = "model",
                 weight_dtype: str = "model",
                 engine_config: Optional[dict] = None,
                 jax_platform: Optional[str] = None):
        if jax_platform:
            # this replica runs there whatever its worker was spawned
            # with (before any array op touches a backend)
            import jax

            jax.config.update("jax_platforms", jax_platform)

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

    async def generate(self, token_lists, max_new_tokens=None,
                       denoising_steps=None, confidence_threshold=None):
        """`denoising_steps`, `confidence_threshold`: a request's own
        fields of a block-diffusion model, handed to `submit` (which
        refuses them for a model that yields a token a step)."""
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
                self.engine.submit(
                    list(t), n_new, timeout_s=budget,
                    denoising_steps=denoising_steps,
                    confidence_threshold=confidence_threshold)
            )
            for t in token_lists
        ]
        return list(await asyncio.gather(*futs))

    async def __call__(self, request):
        body = request.json() if request.body() else {}
        n_new = int(body.get("max_new_tokens", self.max_new_tokens))
        out = await self.generate(
            body["tokens"], n_new, body.get("denoising_steps"),
            body.get("confidence_threshold"))
        # what the device counted for a block-diffusion answer
        counted = ({"forwards": [o.forwards for o in out]}
                   if out and hasattr(out[0], "forwards") else {})
        return {"tokens": out, **counted}

    def stats(self):
        """Queue-depth/TTFT/occupancy signals, piggybacked by the serve
        replica onto health checks: the controller feeds `queue_depth`
        into routing tables (queue-depth-aware pow-2 across replicas)
        and the rest into /api/serve."""
        return self.engine.stats()

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
    return ContinuousLlamaService.bind(model_size=model_size,
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
