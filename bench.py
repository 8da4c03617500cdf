"""Driver benchmarks: single-chip training throughput.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Configs (--config):
- gpt2 (default): BASELINE config #2 — GPT-2 124M pretraining
  (reference: ray/release/air_tests/air_benchmarks), 6*N FLOPs/token.
- llama_lora: BASELINE config #4 — Llama LoRA fine-tune (frozen bf16
  base + rank-8 adapters), 4*N FLOPs/token (no weight-grad matmuls
  for frozen weights).
- rllib_ppo: BASELINE config #3 — RLlib PPO on the new Learner API:
  an EnvRunner fleet streaming object-plane sample refs into a pjit'd
  learner gang with async sample/train overlap (env-steps/s +
  learner updates/s; vs_baseline = overlap-on over the synchronous
  sample→update loop at the identical fleet shape).

`vs_baseline` is measured MFU divided by 0.30 — the
model-flops-utilization a tuned torch run of this size typically
reaches on the reference's GPU path — so >1.0 means the TPU-native
step beats the reference's utilization.
"""

from __future__ import annotations

import json
import time


def _run_timed(step_once, iters, *, tokens_per_iter, flops_per_token,
               metric):
    """Shared warmup + timing + MFU harness; `step_once()` runs one
    compiled train step (managing its own state) and returns the
    metrics dict.  The float() reads force device->host syncs."""
    float(step_once()["loss"])  # warmup / compile

    t0 = time.perf_counter()
    for _ in range(iters - 1):
        step_once()
    float(step_once()["loss"])
    dt = time.perf_counter() - t0

    tokens_per_sec = tokens_per_iter * iters / dt
    mfu = tokens_per_sec * flops_per_token / _peak_flops_per_device()
    print(json.dumps({
        "metric": metric,
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.30, 4),
    }))


# bf16 peak FLOP/s per chip by `device_kind` substring (Google Cloud
# TPU documentation, per-generation system architecture pages)
_PEAK_BF16_FLOPS = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def _require_tpu():
    """The device this process will measure, or an error: these configs
    have no CPU variant — a run without a chip fails instead of timing
    other shapes under another metric name."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; jax.devices()[0] is {d.platform!r} "
            f"({d.device_kind}). Tests and recipes for the CPU are in "
            "tests/ and ray_tpu/scripts/perf.py."
        )
    return d


def _peak_flops_per_device() -> float:
    """bf16 peak FLOP/s of the local chip; a `device_kind` that is not
    in the table is an error, not a default."""
    kind = _require_tpu().device_kind.lower()
    for k, v in _PEAK_BF16_FLOPS.items():
        if k in kind:
            return v
    raise SystemExit(
        f"no published peak FLOP/s for device_kind {kind!r}; add it to "
        "_PEAK_BF16_FLOPS with its source"
    )


def bench_llama_lora() -> None:
    """BASELINE config #4 analog: Llama LoRA fine-tune step on one
    chip (reference: Ray Train Llama-2 7B LoRA, FSDP -> XLA SPMD).
    Frozen bf16 base + rank-8 LoRA adapters, flash attention, full
    remat.  On one v5e-1 (16 GB) the 7B base does not leave working
    room, so the bench runs a 1.4B-class config — the per-chip unit the
    SPMD mesh replicates; MFU is the chip-count-free comparison.
    LoRA FLOPs/token ~= 4*N (fwd 2N + activation-grad backprop 2N; no
    weight-grad matmuls for frozen weights)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama

    _require_tpu()
    cfg = llama.LlamaConfig(
        vocab_size=32000, max_seq_len=1024, dim=2048, n_layers=22,
        n_heads=16, n_kv_heads=16, intermediate=5632,
        attention="flash",
    )
    batch, seq, iters = 8, 1024, 6

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    # the base is FROZEN: no optimizer state, no f32 master needed —
    # store it bf16 (halves base HBM and weight-read bandwidth)
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    lora = llama.init_lora(cfg, jax.random.PRNGKey(1), rank=8)
    opt = optax.adamw(2e-4)
    opt_state = opt.init(lora)
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (batch, seq + 1), 0, cfg.vocab_size,
        dtype=jnp.int32,
    )
    step = jax.jit(
        llama.make_lora_train_step(cfg, opt), donate_argnums=(1, 2)
    )
    state = {"lora": lora, "opt": opt_state}

    def step_once():
        state["lora"], state["opt"], metrics = step(
            params, state["lora"], state["opt"], tokens
        )
        return metrics

    _run_timed(
        step_once, iters, tokens_per_iter=batch * seq,
        flops_per_token=4 * llama.num_params(params),
        metric="llama_1b4_lora_tokens_per_sec_per_chip",
    )


def bench_serve_llm(continuous: bool = False, replicas: int = 1,
                    decode_kernel: str = "auto", kv_dtype: str = "model",
                    weight_dtype: str = "model") -> None:
    """BASELINE config #5 analog: a Llama replica behind serve, driven
    through the FULL data plane (HTTP proxy -> pow-2 router -> replica
    -> @serve.batch -> KV-cached generate), closed-loop clients at
    three concurrency levels (reference: "Ray Serve Llama-3 8B JAX
    replica"; serve composes `pow_2_scheduler.py` + `batching.py` for
    this workload).  On a 16 GB v5e-1 the replica hosts the 1.4B-class
    per-chip unit (same argument as bench_llama_lora); bigger models
    shard over a mesh inside the replica.

    Prints one JSON line; the per-level table (tokens/s, TTFT,
    p50/p99, serve overhead vs bare in-replica `llama.generate`) goes
    to stderr and PERF.md.  vs_baseline = (serve tokens/s at the best
    level / bare generate tokens/s) / 0.85 — i.e. 1.0 means exactly
    the <=15%-overhead target for a full serving data plane; >1.0
    means the data plane costs less than that.

    `continuous=True` serves the SAME workload through the
    continuous-batching engine (`serve/llm_engine.py`, the vLLM-on-Ray
    pattern): requests join a resident decode batch mid-flight, so the
    denominator stays the gather-config's bare ceiling and vs_baseline
    directly shows the scheduling win.

    `replicas=N` (continuous mode) deploys N engine replicas behind the
    queue-depth-aware router — the scale-out axis once one replica's
    tick rate saturates a core (PERF.md: ~2,370 tok/s single-replica
    ceiling).  Concurrency levels and request counts scale with N so
    the fleet actually saturates; `vs_baseline` stays against ONE
    bare-generate replica, so N-replica aggregate shows directly as
    >1.
    """
    import concurrent.futures as cf
    import statistics
    import sys
    import urllib.request

    # The DRIVER stays off JAX: the serve replica (a worker process
    # holding the `TPU` lease) is the chip's only owner, and it is the
    # replica that says which device it measured (checked below).
    if replicas > 1 and not continuous:
        raise ValueError("--replicas applies to the continuous "
                         "(serve_llm_cb) config")
    # max_batch 16 measured BEST through the full data plane even
    # though bare generate keeps scaling (B=16/32/64 -> 1847/2622/
    # 3163 tok/s): at max_batch 32 / c=64 the batcher forms ragged
    # pow-2 groups that serialize per cycle and queueing spikes
    # (measured 1425 tok/s, +34% overhead, p99 3.0 s vs 1453,
    # +5.5%, p99 0.72 s at 16) — batched-decode throughput only
    # helps serving if the batcher can actually FILL the batches.
    # The continuous engine has no such limit: slots stay full.
    model_size, prompt_len, n_new, max_batch = "llama1b4", 128, 32, 16
    levels = (1, 8, 32, 64) if continuous else (1, 8, 32)
    metric = ("serve_llama1b4_cb_tokens_per_sec" if continuous
              else "serve_llama1b4_tokens_per_sec")
    if replicas > 1:
        # saturation needs proportional offered load; keep the ladder's
        # lower rungs for the latency picture
        levels = tuple(c * replicas for c in levels)
        metric += f"_x{replicas}"
    engine_knobs = (decode_kernel, kv_dtype, weight_dtype)
    if engine_knobs != ("auto", "model", "model"):
        if not continuous:
            raise ValueError("--decode-kernel/--kv-dtype/--weight-dtype "
                             "apply to the continuous (serve_llm_cb) "
                             "config")
        # distinct metric names per decode/quantization variant, so
        # PERF.md rows never silently overwrite each other
        if decode_kernel != "auto":
            metric += f"_{decode_kernel}"
        if kv_dtype == "int8":
            metric += "_kv8"
        if weight_dtype == "int8":
            metric += "_w8"

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.examples.serve_llm import (
        ContinuousLlamaService,
        LlamaService,
    )

    rt.init(num_workers=4, num_cpus=16)
    try:
        if continuous:
            app = ContinuousLlamaService.options(
                num_replicas=replicas, autoscaling_config=None,
                max_ongoing_requests=256,
                health_check_timeout_s=120.0,
            ).bind(model_size=model_size, max_new_tokens=n_new,
                   slots=32, chunk=8,
                   # max_len caps ONE sequence (prompt + budget + chunk
                   # slack).  The KV cache is paged now, so this no
                   # longer taxes per-step time — but it still sizes
                   # the default pool budget (HBM)
                   max_len=prompt_len + n_new + 8 + 8,
                   block_size=16,
                   decode_kernel=decode_kernel, kv_dtype=kv_dtype,
                   weight_dtype=weight_dtype)
        else:
            app = LlamaService.options(
                num_replicas=1, autoscaling_config=None,
                max_ongoing_requests=64, health_check_timeout_s=120.0,
            ).bind(model_size=model_size, max_new_tokens=n_new,
                   max_batch_size=max_batch)
        handle = serve.run(app, name="llm", route_prefix="/llm",
                           timeout_s=900.0)

        # Bare in-replica baseline: the no-serve ceiling the overhead
        # is computed against.  Gather mode also pre-compiles every
        # [bucket, T] shape its padded batcher can produce; the
        # continuous engine compiles its own programs on first use
        # (warmed below), so one baseline batch size suffices there.
        if continuous:
            bare = {max_batch: handle.bench_direct.remote(
                max_batch, prompt_len, n_new, iters=3,
            ).result(timeout_s=1800.0)}
        else:
            bare = {}
            b = 1
            while b <= max_batch:
                bare[b] = handle.bench_direct.remote(
                    b, prompt_len, n_new, iters=3
                ).result(timeout_s=1800.0)
                b *= 2
        bare_tok_s = bare[max_batch]["tokens_per_sec"]
        # the replica's own report of where it ran
        device = bare[max_batch]["device"]
        if device["platform"] != "tpu":
            raise SystemExit(
                f"bench.py needs a TPU; the replica ran on {device}"
            )

        host, port = serve.http_address()
        url = f"http://{host}:{port}/llm"
        prompt = list(range(1, prompt_len + 1))

        def one_request(n: int = n_new) -> float:
            body = json.dumps({"tokens": [prompt],
                               "max_new_tokens": n}).encode()
            req = urllib.request.Request(url, data=body, method="POST")
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=600) as r:
                out = json.loads(r.read())
            dt = time.perf_counter() - t0
            assert len(out["tokens"][0]) == n
            return dt

        # TTFT at c=1: prefill + 1 token through the full data plane
        # (its own (T, 1) shape — warm it, then measure)
        one_request(1)
        ttft = [one_request(1) for _ in range(8)]

        results = {}
        for c in levels:
            n_reqs = max(20, c * 10)
            per = n_reqs // c

            def client(_):
                return [one_request() for _ in range(per)]

            with cf.ThreadPoolExecutor(c) as pool:  # warm this level
                list(pool.map(lambda _: one_request(), range(c)))
            t0 = time.perf_counter()
            with cf.ThreadPoolExecutor(c) as pool:
                lat = [d for ds in pool.map(client, range(c)) for d in ds]
            wall = time.perf_counter() - t0
            lat.sort()
            results[c] = {
                "tokens_per_sec": len(lat) * n_new / wall,
                "p50_s": lat[len(lat) // 2],
                "p99_s": lat[min(len(lat) - 1, int(len(lat) * 0.99))],
                "requests": len(lat),
            }
            print(f"# c={c}: {results[c]['tokens_per_sec']:.0f} tok/s, "
                  f"p50 {results[c]['p50_s'] * 1e3:.0f} ms, "
                  f"p99 {results[c]['p99_s'] * 1e3:.0f} ms",
                  file=sys.stderr)

        best = max(r["tokens_per_sec"] for r in results.values())
        print(f"# bare generate (batch {max_batch}): {bare_tok_s:.0f} tok/s;"
              f" serve overhead at best level: {1 - best / bare_tok_s:+.1%};"
              f" TTFT p50 {statistics.median(ttft) * 1e3:.0f} ms",
              file=sys.stderr)
        record = {
            "metric": metric,
            "value": round(best, 2),
            "unit": "tokens/s",
            "vs_baseline": round(best / bare_tok_s / 0.85, 4),
            "device": device,
        }
        if replicas > 1:
            record["replicas"] = replicas
            record["per_replica_tokens_per_sec"] = round(best / replicas, 2)
        print(json.dumps(record))
    finally:
        serve.shutdown()
        rt.shutdown()


def bench_rllib_ppo(num_runners: int = 8) -> None:
    """BASELINE config #3: RLlib PPO, new Learner API — the EnvRunner
    fleet shape (>=8 CPU sampling actors, vectorized envs, sample
    batches as object-plane references) feeding a >=2-device pjit
    learner gang, with async sample/train overlap.

    Env runners are numpy CPU actors by design (the reference samples
    on CPU workers too), so the learner gang runs on the host-CPU
    device mesh here — on a pod, `config.mesh` maps the same compiled
    update onto TPU devices.  `vs_baseline` is the async-overlap
    throughput over the reference's synchronous sample→update loop
    measured at the IDENTICAL fleet shape: >1.0 means the overlap
    hides sampling wall-time the sync loop pays serially.  The
    per-mode rows (overlap ratio, exactly-once accounting) go to
    stderr and PERF.md."""
    import sys

    from ray_tpu.rllib.bench import measure_rllib_ppo

    rows = measure_rllib_ppo(
        num_runners=num_runners, envs_per_runner=16, rollout_len=64,
        minibatch=2048, epochs=2, gang_devices=4, iters=4,
        compare_sync=True, include_dag=True,
    )
    a, s = rows["rllib_ppo"], rows["rllib_ppo_sync"]
    d = rows["rllib_ppo_dag"]
    for name, row in (("overlap", a), ("sync", s),
                      ("compiled-dag", d)):
        print(
            f"# {name}: {row['env_steps_per_s']:.0f} env-steps/s, "
            f"{row['updates_per_s']:.1f} updates/s, "
            f"overlap_ratio {row.get('overlap_ratio', 0.0):.2f}, "
            f"accounting_exact {row['accounting_exact']:.0f}, "
            f"runners {row['runners']:.0f}, "
            f"gang {row['gang_devices']:.0f}",
            file=sys.stderr,
        )
    assert a["accounting_exact"] == 1.0 and s["accounting_exact"] == 1.0
    assert d["accounting_exact"] == 1.0
    print(json.dumps({
        "metric": "rllib_ppo_env_steps_per_sec",
        "value": round(a["env_steps_per_s"], 2),
        "unit": "env_steps/s",
        "vs_baseline": round(
            a["env_steps_per_s"] / s["env_steps_per_s"], 4
        ),
        "learner_updates_per_sec": round(a["updates_per_s"], 2),
        "overlap_ratio": round(a["overlap_ratio"], 4),
        "num_env_runners": int(a["runners"]),
        "gang_devices": int(a["gang_devices"]),
        # compiled-DAG learner round (use_compiled_dag=True): sample
        # hop + weights broadcast over shm tensor channels.  Reported
        # as its own delta vs the RPC overlap row, win or not.
        "dag_env_steps_per_sec": round(d["env_steps_per_s"], 2),
        "dag_updates_per_sec": round(d["updates_per_s"], 2),
        "dag_overlap_ratio": round(d["overlap_ratio"], 4),
        "dag_vs_rpc_overlap": round(
            d["env_steps_per_s"] / a["env_steps_per_s"], 4
        ),
    }))


def main() -> None:
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config",
                   choices=["gpt2", "llama_lora", "serve_llm",
                            "serve_llm_cb", "rllib_ppo"],
                   default="gpt2")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve_llm_cb only: deploy N engine replicas "
                        "behind the queue-depth-aware router and "
                        "saturate the fleet")
    p.add_argument("--runners", type=int, default=8,
                   help="rllib_ppo only: env-runner fleet size")
    p.add_argument("--decode-kernel", default="auto",
                   choices=["auto", "pallas", "gather"],
                   help="serve_llm_cb only: engine decode route "
                        "(auto = fused Pallas kernel on TPU, gather "
                        "elsewhere)")
    p.add_argument("--kv-dtype", default="model",
                   choices=["model", "int8"],
                   help="serve_llm_cb only: KV block-pool storage "
                        "dtype (int8 = half payload + f32 scales)")
    p.add_argument("--weight-dtype", default="model",
                   choices=["model", "int8"],
                   help="serve_llm_cb only: serve int8-quantized "
                        "weights (per-output-channel scales)")
    args = p.parse_args()
    if args.replicas > 1 and args.config != "serve_llm_cb":
        p.error("--replicas applies only to --config serve_llm_cb")
    if args.runners != 8 and args.config != "rllib_ppo":
        p.error("--runners applies only to --config rllib_ppo")
    knobs = (args.decode_kernel, args.kv_dtype, args.weight_dtype)
    if knobs != ("auto", "model", "model") and args.config != "serve_llm_cb":
        p.error("--decode-kernel/--kv-dtype/--weight-dtype apply only "
                "to --config serve_llm_cb")
    if args.config == "llama_lora":
        bench_llama_lora()
        return
    if args.config == "serve_llm":
        bench_serve_llm()
        return
    if args.config == "serve_llm_cb":
        bench_serve_llm(continuous=True, replicas=args.replicas,
                        decode_kernel=args.decode_kernel,
                        kv_dtype=args.kv_dtype,
                        weight_dtype=args.weight_dtype)
        return
    if args.config == "rllib_ppo":
        bench_rllib_ppo(num_runners=args.runners)
        return
    bench_gpt2()


def bench_gpt2() -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2

    _require_tpu()
    # flash (Pallas) with the SINGLE-TILE FUSED backward (dq/dk/dv
    # in one kernel sharing the s/p/ds recompute + in-kernel delta)
    # + bf16 lm-head logits + full remat; batch 35 measured best
    # with the fused bwd (32: 92.3k, 34: 96.7k, 35: 98.1k,
    # 36: 95.5k tok/s on v5e-1)
    cfg = gpt2.GPT2Config(attention="flash", logits_dtype=jnp.bfloat16)
    batch, seq, iters = 35, 1024, 6

    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    opt = gpt2.default_optimizer(total_steps=1000)
    opt_state = opt.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size, dtype=jnp.int32
    )

    step = jax.jit(gpt2.make_train_step(cfg, opt), donate_argnums=(0, 1))
    state = {"params": params, "opt": opt_state}

    def step_once():
        state["params"], state["opt"], metrics = step(
            state["params"], state["opt"], tokens
        )
        return metrics

    # 6*N FLOPs/token fwd+bwd (PaLM appendix convention, non-attn)
    _run_timed(
        step_once, iters, tokens_per_iter=batch * seq,
        flops_per_token=6 * gpt2.num_params(state["params"]),
        metric="gpt2_124m_train_tokens_per_sec_per_chip",
    )


if __name__ == "__main__":
    main()
