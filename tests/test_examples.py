"""Example-program tests (the baseline-config parity demos)."""

import pytest

import ray_tpu as rt


@pytest.fixture(scope="module")
def cluster():
    rt.init(num_workers=3, num_cpus=8, ignore_reinit_error=True)
    yield
    rt.shutdown()


def test_mnist_fashion_ddp(cluster, tmp_path):
    """BASELINE config #1: 2-worker data-parallel MLP training."""
    from ray_tpu.examples import mnist

    result = mnist.run(num_workers=2, epochs=4,
                       storage_path=str(tmp_path / "mnist"))
    assert result.error is None
    assert result.metrics["epoch"] == 3
    # the synthetic teacher task is learnable: well above 10% chance
    assert result.metrics["accuracy"] > 0.5, result.metrics


def test_serve_llm_example(cluster):
    """BASELINE #5 shape: Llama JAX replica behind serve — handle calls
    and HTTP, batched KV-cached generation, deterministic output."""
    import json
    import urllib.request

    from ray_tpu import serve
    from ray_tpu.examples.serve_llm import run

    handle = run(model_size="tiny", max_new_tokens=5, jax_platform="cpu")
    try:
        prompts = [[1, 2, 3, 4], [9, 8, 7, 6]]
        out = handle.generate.remote(prompts).result(timeout_s=120)
        assert len(out) == 2 and all(len(t) == 5 for t in out)
        # deterministic greedy decode: same prompt -> same tokens
        again = handle.generate.remote(prompts).result(timeout_s=60)
        assert again == out

        # HTTP surface
        host, port = serve.http_address()
        req = urllib.request.Request(
            f"http://{host}:{port}/llm",
            data=json.dumps({"tokens": [[1, 2, 3, 4]],
                             "max_new_tokens": 5}).encode(),
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            body = json.loads(r.read())
        assert body["tokens"][0] == out[0]
    finally:
        serve.delete("llm")


def test_serve_llm_example_runs_the_engine(cluster):
    """`run()`'s app IS the continuous-batching engine (the one LLM
    serve path): the handle answers `stats()` with the engine's own
    panel, its count of finished requests grows with the requests
    served, and greedy tokens equal `llama.generate`'s on the model the
    deployment builds from the same seed."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.examples.serve_llm import _build_model, run
    from ray_tpu.models import llama

    cfg, params = _build_model("tiny", seed=0)
    prompts = [[5, 4, 3, 2, 1], [7, 7, 7]]
    handle = run(model_size="tiny", max_new_tokens=6, jax_platform="cpu")
    try:
        before = handle.stats.remote().result(timeout_s=120)
        assert before["decode_kernel"] == "gather"  # what "auto" is off the chip
        out = handle.generate.remote(prompts).result(timeout_s=120)
        for prompt, got in zip(prompts, out):
            want = np.asarray(llama.generate(
                cfg, params, jnp.asarray([prompt], jnp.int32), 6))[0]
            assert got == [int(t) for t in want]
        after = handle.stats.remote().result(timeout_s=60)
        assert (after["finished_total"] - before["finished_total"]
                == len(prompts))
    finally:
        serve.delete("llm")


def test_ppo_pixels_example(cluster):
    """BASELINE config #3 parity demo: the example's OWN wiring must
    produce a learning signal, not merely run — a mis-wired connector
    or encoder would still 'train' with flat returns.  Random policy
    on Catch scores ~0 (±small); a few iterations of the example's
    exact config must beat that margin decisively.  Full convergence
    (return ~1.0) stays in test_rllib.py::test_ppo_learns_pixel_catch;
    this bar is set low enough to stay cheap and stable."""
    import numpy as np

    from ray_tpu.examples import ppo_pixels

    # early-exits the moment the bar is crossed (typically well under
    # the iteration cap), keeping this cheaper than the full-convergence
    # rllib test while still failing on a silent wiring regression
    result = ppo_pixels.run(iterations=45, target_return=0.35, seed=0)
    assert np.isfinite(result["total_loss"])
    assert result["num_env_steps_sampled"] > 0
    assert result["best_return"] >= 0.35, (
        f"no learning signal from the example config: best return "
        f"{result['best_return']}"
    )
