"""Overload chaos: spike storms against a bounded serve plane.

The acceptance storm (ISSUE 10): under a burst far beyond capacity
against ONE replica, the admission queue stays bounded at
`max_queued`, overflow is rejected immediately with typed
backpressure, queued requests whose deadline expired are shed BEFORE
prefill, the KV block pool returns to its pre-storm free count, and
every ADMITTED request's greedy output stays bit-identical to a
dedicated `llama.generate`.  Engine-level rounds run three times
back-to-back (determinism under repetition); the HTTP round drives
the same storm through the full proxy -> router -> replica -> engine
path and checks the 503 + Retry-After boundary.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import ray_tpu as rt  # noqa: E402
from ray_tpu import exceptions as exc  # noqa: E402
from ray_tpu import serve  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.serve.llm_engine import LlamaEngine  # noqa: E402


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _expected(cfg, params, prompt, n_new):
    out = llama.generate(
        cfg, params, jnp.asarray([prompt], jnp.int32), n_new
    )
    return [int(t) for t in np.asarray(out)[0]]


def test_engine_spike_storm_bounded_queue_no_leaks(model):
    """Three consecutive 10x-burst rounds against one bounded engine:
    exact admission accounting, prompt sub-100ms rejections, sheds
    never reaching prefill, zero block-pool leaks, and bit-identical
    admitted outputs — every round."""
    cfg, params = model
    slots, cap = 2, 4
    eng = LlamaEngine(cfg, params, slots=slots, max_len=48, chunk=2,
                      block_size=8, prefix_cache=False, max_queued=cap)
    try:
        rng = np.random.RandomState(7)

        def _prompt():
            return [int(x) for x in rng.randint(1, cfg.vocab_size,
                                                size=17)]

        # warm the compiled families so storm timing is steady-state
        for f in [eng.submit(_prompt(), 4) for _ in range(slots)]:
            f.result(timeout=300)
        idle = eng.stats()
        free0 = idle["blocks_free"]
        assert free0 == idle["blocks_total"]  # prefix off, engine idle

        for round_ in range(3):
            base = eng.stats()
            # saturate both slots with long decodes (>= 10 chunk walls)
            longs = [(p := _prompt(), eng.submit(p, 20)) for _ in
                     range(slots)]
            deadline = time.monotonic() + 60
            while eng.stats()["free_slots"] > 0:
                assert time.monotonic() < deadline, "never saturated"
                # deterministic local poll, not retry pacing
                time.sleep(0.001)  # rtlint: disable=RT006
            # expired wave: queues now, must be SHED at pop time —
            # before any prefill dispatch
            sheds = [eng.submit(_prompt(), 4, timeout_s=0.001)
                     for _ in range(3)]
            # overflow wave: 10x the remaining capacity; the queue is
            # bounded so most of these must reject IMMEDIATELY
            t0 = time.perf_counter()
            overflow = [(p := _prompt(), eng.submit(p, 4))
                        for _ in range(10)]
            # rejection latency: with the queue at its cap, one more
            # submit resolves rejected in-line — never via the engine
            # thread, never after a queueing delay
            probe = eng.submit(_prompt(), 4)
            probe_latency = time.perf_counter() - t0
            assert probe.done(), "over-cap submit did not resolve inline"
            with pytest.raises(exc.BackPressureError) as ei:
                probe.result()
            assert ei.value.retry_after_s > 0
            assert probe_latency < 0.1, (
                f"rejection took {probe_latency * 1e3:.1f} ms"
            )

            queue_peak = 0
            waves = [f for _p, f in longs] + sheds \
                + [f for _p, f in overflow]
            while not all(f.done() for f in waves):
                queue_peak = max(queue_peak, eng.stats()["queued"])
                # deterministic local poll, not retry pacing
                time.sleep(0.002)  # rtlint: disable=RT006
            # bounded queue: never past the cap, at any sampled instant
            assert queue_peak <= cap

            admitted = rejected = shed = 0
            for prompt, f in longs + overflow:
                try:
                    got = f.result(timeout=60)
                    admitted += 1
                    # bit-identical outputs for every admitted request
                    n_new = 20 if (prompt, f) in longs else 4
                    assert got == _expected(cfg, params, prompt, n_new)
                except exc.BackPressureError:
                    rejected += 1
            for f in sheds:
                with pytest.raises(exc.DeadlineExceededError):
                    f.result(timeout=60)
                shed += 1
            s = eng.stats()
            # exact conservation: every offered request is accounted
            # exactly once (the probe adds one more rejection)
            assert admitted + rejected + shed == len(waves)
            assert shed == 3 and rejected >= 6
            assert s["rejected_total"] - base["rejected_total"] == \
                rejected + 1
            assert s["shed_total"] - base["shed_total"] == 3
            # sheds never reached prefill: the requests prefilled are
            # ONLY the admitted ones, in no more programs than that
            assert s["prefill_rows"] - base["prefill_rows"] == admitted
            assert 0 < (s["prefill_calls"] - base["prefill_calls"]) \
                <= admitted
            # the pool is back to its pre-storm free count, no leaks
            assert s["blocks_free"] == free0
            assert s["active"] == 0 and s["queued"] == 0
    finally:
        eng.shutdown()


def test_idle_engine_with_stale_ttft_ema_still_admits(model):
    """Predictive shedding is gated on the engine being BUSY: the TTFT
    EMA is lifetime-smoothed and never decays while idle, so a
    storm-inflated EMA must not shed deadline-carrying requests from
    an idle engine forever (sheds never update the EMA — nothing
    would ever bring it back down)."""
    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=2, max_len=48, chunk=2,
                      block_size=8, prefix_cache=False)
    try:
        rng = np.random.RandomState(13)
        prompt = [int(x) for x in rng.randint(1, cfg.vocab_size,
                                              size=12)]
        eng.submit(prompt, 4).result(timeout=300)  # warm, then idle
        eng._ttft_ema_s = 999.0  # a storm left the EMA sky-high
        got = eng.submit(prompt, 4, timeout_s=5.0).result(timeout=300)
        assert got == _expected(cfg, params, prompt, 4)
        assert eng.stats()["shed_predicted"] == 0
    finally:
        eng.shutdown()


def test_engine_drain_finishes_live_sequences(model):
    """begin_drain(): new submissions reject with BackPressureError,
    live sequences decode to completion (bit-identical), shutdown
    returns every block to the pool."""
    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=2, max_len=48, chunk=2,
                      block_size=8, prefix_cache=False)
    try:
        rng = np.random.RandomState(11)
        prompt = [int(x) for x in rng.randint(1, cfg.vocab_size,
                                              size=12)]
        live = eng.submit(prompt, 10)
        eng.begin_drain()
        rejected = eng.submit(prompt, 4)
        with pytest.raises(exc.BackPressureError):
            rejected.result(timeout=10)
        assert live.result(timeout=300) == _expected(
            cfg, params, prompt, 10
        )
        s = eng.stats()
        assert s["draining"] == 1.0
        assert s["blocks_free"] == s["blocks_total"]
    finally:
        eng.shutdown()


# ----------------------------------------------------------------------
# the full-path HTTP storm
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cluster():
    rt.init(num_workers=4, num_cpus=16, ignore_reinit_error=True)
    yield
    serve.shutdown()
    rt.shutdown()


@pytest.fixture()
def serve_instance(cluster):
    yield
    for app in list(serve.status()):
        serve.delete(app)


def test_http_spike_storm_503s_and_engine_recovers(serve_instance):
    """10x HTTP burst against a 1-replica bounded engine deployment:
    admitted requests return bit-identical tokens, overflow gets 503 +
    Retry-After through the proxy, and after the storm the engine's
    block pool and queue are back to idle."""
    from ray_tpu.examples.serve_llm import ContinuousLlamaService, _build_model

    # the SAME (cfg, params) the deployment builds: bit-identity is
    # against the deployed model, not the test fixture's
    cfg, params = _build_model("tiny", seed=0)
    slots, cap = 2, 4
    app = ContinuousLlamaService.options(
        num_replicas=1, autoscaling_config=None,
        max_ongoing_requests=64, max_queued_requests=cap,
        health_check_timeout_s=120.0,
    ).bind(model_size="tiny", max_new_tokens=4, slots=slots, chunk=2,
           max_len=40, block_size=8, prefix_cache=False,
           max_queued=cap, jax_platform="cpu")
    serve.run(app, name="storm", route_prefix="/storm",
              timeout_s=300.0)
    host, port = serve.http_address()
    url = f"http://{host}:{port}/storm"
    prompt = list(range(1, 13))
    expected = _expected(cfg, params, prompt, 4)
    body = json.dumps({"tokens": [prompt], "max_new_tokens": 4}).encode()

    # one warm request (compiles prefill+chunk) so the storm hits a
    # steady-state engine
    req = urllib.request.Request(url, data=body, method="POST")
    with urllib.request.urlopen(req, timeout=300) as r:
        assert json.loads(r.read())["tokens"][0] == expected

    results = []
    lock = threading.Lock()

    def _one():
        t0 = time.monotonic()
        try:
            rq = urllib.request.Request(url, data=body, method="POST")
            with urllib.request.urlopen(rq, timeout=120) as r:
                out = (r.status, json.loads(r.read()), None,
                       time.monotonic() - t0)
        except urllib.error.HTTPError as e:
            out = (e.code, e.read().decode(errors="replace"),
                   e.headers.get("Retry-After"),
                   time.monotonic() - t0)
        with lock:
            results.append(out)

    # 10x burst: 2 slots + 4 queue against 24 concurrent requests
    threads = [threading.Thread(target=_one) for _ in range(24)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert len(results) == 24
    oks = [r for r in results if r[0] == 200]
    rejects = [r for r in results if r[0] == 503]
    assert len(oks) + len(rejects) == 24, results
    assert oks and rejects, results
    for _status, payload, _ra, _el in oks:
        # bit-identical through the whole data plane, storm or not
        assert payload["tokens"][0] == expected
    for _status, text, retry_after, _el in rejects:
        assert retry_after is not None and int(retry_after) >= 1
        assert "retry_after_s" in text

    # after the storm: pool back to idle, queue empty, and the
    # engine's rejection counters visible through the controller
    from ray_tpu.serve.api import _get_controller

    controller = _get_controller()
    deadline = time.time() + 60
    engine_stats = {}
    while time.time() < deadline:
        per = rt.get(controller.get_replica_metrics.remote())
        reps = per.get("storm", {}).get("ContinuousLlamaService", {})
        engine_stats = next(
            (m.get("user_stats") or {} for m in reps.values()), {}
        )
        # the piggyback refreshes on the health cadence: wait for a
        # POST-storm snapshot (rejections visible) that is idle again,
        # not a stale pre-storm one that is trivially clean
        if (engine_stats.get("rejected_total", 0) >= len(rejects)
                and engine_stats.get("active") == 0
                and engine_stats.get("queued") == 0
                and engine_stats.get("blocks_free")
                == engine_stats.get("blocks_total")):
            break
        time.sleep(0.3)
    assert engine_stats.get("active") == 0
    assert engine_stats.get("queued") == 0
    assert engine_stats.get("blocks_free") == \
        engine_stats.get("blocks_total"), engine_stats
    assert engine_stats.get("rejected_total", 0) >= len(rejects)
    status = rt.get(controller.get_serve_status.remote())
    overload = status["storm"]["ContinuousLlamaService"]["overload"]
    assert overload["rejected_total"] >= len(rejects)
