"""Per-request serve telemetry units (no cluster): the latency
ledger's phase accounting, tail-based span retention, the
zero-allocation gate, the engine's windowed TTFT percentile + tick
introspection ring, and the SLO burn-rate math
(`serve/request_ledger.py`, `serve/slo.py`)."""

import time

import pytest

from ray_tpu.metrics import metric_defs as mdefs
from ray_tpu.serve import request_ledger as rl
from ray_tpu.serve import slo
from ray_tpu.util import tracing


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts with both consumers off and empty per-process
    aggregation state, and leaves the same way."""
    rl._reset_for_tests()
    _hist_base.update({name: _hist_total(name) for name in _HISTS})
    yield
    rl._reset_for_tests()
    mdefs.set_enabled(False)
    tracing.disable()
    tracing.clear_spans()


# histogram counts are process-wide and survive `_reset_for_tests`:
# whatever an earlier test in this process observed (the perf-harness
# serve rows do) is the baseline, and a test counts only its own
_HISTS = ("rt_serve_e2e_seconds", "rt_serve_queue_wait_seconds",
          "rt_serve_ttft_seconds", "rt_serve_tpot_seconds",
          "rt_serve_prefill_seconds")
_hist_base: dict = {}


def _hist_total(name):
    return sum(v for labels, v in mdefs.metric(name)._samples()
               if "__count__" in labels)


def _hist_count(name):
    return _hist_total(name) - _hist_base.get(name, 0.0)


# ----------------------------------------------------------------------
# ledger: gating + phase accounting
# ----------------------------------------------------------------------
def test_disabled_ledger_allocates_nothing():
    """THE hot-loop contract: with metrics and tracing both off, no
    ledger or ticket object is ever built — every serve call site is a
    `led is not None` test on None."""
    assert not rl.enabled()
    assert rl.start_request("http", "a", "d") is None
    assert rl.engine_ticket() is None
    with rl.use_ledger(None) as led:  # no-op CM, no token set
        assert led is None
        assert rl.current() is None


def test_phase_durations_sum_to_e2e_exactly():
    mdefs.set_enabled(True)
    led = rl.start_request("http", "app", "dep", "r0")
    assert led is not None
    t = led.t0
    led.begin("proxy", now=t)
    led.begin("queue_wait", now=t + 0.010)
    led.begin("backend", now=t + 0.025)
    e2e = led.finish("ok", now=t + 0.100)
    assert e2e == pytest.approx(0.100)
    assert [p[0] for p in led.phases] == ["proxy", "queue_wait",
                                         "backend"]
    # contiguity is structural: each phase starts where the previous
    # ended, so the durations sum to e2e with no gaps to hide time in
    for (_, _, e_prev), (_, s_next, _) in zip(led.phases, led.phases[1:]):
        assert e_prev == s_next
    assert sum(te - ts for _, ts, te in led.phases) == pytest.approx(e2e)
    # terminal is idempotent: a second finish neither re-observes nor
    # rewrites the timeline
    assert led.finish("error", now=t + 9.0) == pytest.approx(0.100)
    assert led.status == "ok"
    assert _hist_count("rt_serve_e2e_seconds") == 1.0
    assert _hist_count("rt_serve_queue_wait_seconds") == 1.0


def test_refused_terminal_phase_and_reason_tag():
    mdefs.set_enabled(True)
    led = rl.start_request("http", "app", "dep", "r0")
    led.begin("proxy")
    led.finish("rejected", "queue_full")
    assert led.status == "rejected" and led.reason == "queue_full"
    name, ts, te = led.phases[-1]
    assert name == "terminal:rejected" and ts == te  # zero-duration
    # shed classifies the same way through a second ledger
    led2 = rl.start_request("http", "app", "dep", "r0")
    led2.finish("shed", "shed_predicted")
    assert led2.phases[-1][0] == "terminal:shed"
    assert led2.reason == "shed_predicted"


def test_engine_ticket_notes_and_phase_spans():
    mdefs.set_enabled(True)
    led = rl.start_request("replica", "app", "dep", "r0")
    with rl.use_ledger(led):
        tk = rl.engine_ticket()
    assert tk is not None and tk.ledger is led
    t = tk.t_submit
    tk.admitted(t + 0.010)
    tk.prefilled(t + 0.030)
    tk.first_token(t + 0.032)
    tk.done(5, now=t + 0.072)
    assert led.notes["ttft_s"] == pytest.approx(0.032, abs=1e-5)
    assert led.notes["prefill_s"] == pytest.approx(0.020, abs=1e-5)
    # 4 tokens after the first over 40 ms -> 10 ms/token
    assert led.notes["tpot_s"] == pytest.approx(0.010, abs=1e-5)
    assert led.notes["n_tokens"] == 5
    led.finish("ok", now=t + 0.080)
    assert _hist_count("rt_serve_ttft_seconds") == 1.0
    assert _hist_count("rt_serve_tpot_seconds") == 1.0
    assert _hist_count("rt_serve_prefill_seconds") == 1.0


def test_engine_ticket_refused_stamps_reason():
    mdefs.set_enabled(True)
    led = rl.start_request("replica", "app", "dep", "r0")
    with rl.use_ledger(led):
        tk = rl.engine_ticket()
    tk.refused("queue_full")
    led.finish("rejected", "queue_full")
    assert led.notes["engine_refused"] == "queue_full"
    assert led.phases[-1][0] == "terminal:rejected"


# ----------------------------------------------------------------------
# tail-based span retention under head-sampling
# ----------------------------------------------------------------------
def test_tail_capture_retains_slowest_and_refused(monkeypatch):
    """RT_TRACE_SAMPLE=0 drops every head-sampling roll — yet the
    slowest-K% and every refused request must still land their span
    trees (the whole point of deferring the commit to terminal time)."""
    monkeypatch.setenv("RT_TRACE_SAMPLE", "0")
    tracing.enable()
    tracing.clear_spans()

    def _req(e2e_s, status="ok", reason=None):
        led = rl.start_request("http", "app", "dep", "r0")
        assert led is not None and not led.sampled
        led.begin("proxy", now=led.t0)
        led.finish(status, reason, now=led.t0 + e2e_s)
        return led

    def _roots():
        return [s for s in tracing.get_spans()
                if s["name"] == "serve.request:dep"]

    # seed the tail ring to TAIL_MIN_SAMPLES with fast requests (below
    # the threshold count nothing qualifies as tail), then probe BELOW
    # the ring's slowest: none sampled, none tail, none refused ->
    # nothing records
    for _ in range(rl.TAIL_MIN_SAMPLES):
        _req(0.010)
    for _ in range(4):
        _req(0.005)
    assert _roots() == []
    # a request far above the ring's (100-K)th percentile is retained
    # with its phase children under the unsampled root
    slow = _req(1.0)
    roots = _roots()
    assert len(roots) == 1
    assert roots[0]["trace_id"] == slow.trace_id
    assert roots[0]["attrs"]["status"] == "ok"
    kids = [s for s in tracing.get_spans()
            if s.get("parent_id") == slow.root_id]
    assert any(s["name"] == "serve.proxy" for s in kids)
    # ... while a fast request right after still drops
    _req(0.001)
    assert len(_roots()) == 1
    # ANY refused request force-retains, whatever its latency, and the
    # terminal phase + reason ride the tree
    shed = _req(0.001, status="shed", reason="shed_predicted")
    roots = _roots()
    assert len(roots) == 2
    mine = [s for s in roots if s["trace_id"] == shed.trace_id][0]
    assert mine["error"] == "shed_predicted"
    assert any(s["name"] == "serve.terminal:shed"
               for s in tracing.get_spans()
               if s["trace_id"] == shed.trace_id)


def test_sampled_request_keeps_full_tree(monkeypatch):
    monkeypatch.setenv("RT_TRACE_SAMPLE", "1")
    tracing.enable()
    tracing.clear_spans()
    led = rl.start_request("http", "app", "dep", "r0")
    assert led.sampled
    led.begin("proxy")
    led.finish("ok")
    assert [s for s in tracing.get_spans()
            if s["name"] == "serve.request:dep"]


# ----------------------------------------------------------------------
# SLO burn-rate math (serve/slo.py)
# ----------------------------------------------------------------------
def test_slo_config_validation_and_budget():
    cfg = slo.SLOConfig(target_ttft_s=0.5, objective=0.99)
    assert cfg.has_any()
    assert cfg.error_budget == pytest.approx(0.01)
    assert not slo.SLOConfig().has_any()
    with pytest.raises(ValueError):
        slo.SLOConfig(target_ttft_s=-1.0)
    with pytest.raises(ValueError):
        slo.SLOConfig(target_e2e_s=1.0, objective=2.0)


def test_slo_counters_fold_and_burn():
    """Replica counter blocks fold into burn rates: a fleet breaching
    its e2e target burns budget at a rate >> 1; restarts (counters
    going backwards) clamp to zero instead of poisoning the window."""
    cfg = slo.SLOConfig(target_e2e_s=0.1, objective=0.99,
                        windows=(60,))
    tr = slo.BurnRateTracker()
    t0 = time.time() - 30.0
    blk = slo.empty_counters()
    for _ in range(100):
        blk["n"] += 1
        blk["e2e"][slo.bucket_index(5.0)] += 1  # every request slow
    tr.fold("r0", blk)
    tr.snapshot(now=t0)
    blk2 = {k: (list(v) if isinstance(v, list) else v)
            for k, v in blk.items()}
    blk2["n"] += 50
    blk2["e2e"][slo.bucket_index(5.0)] += 50
    tr.fold("r0", blk2)
    tr.snapshot(now=t0 + 20.0)
    st = slo.status_for(tr, cfg)
    assert st["configured"] and st["requests_total"] == 150
    burn = st["windows"]["60"]["e2e_burn"]
    # 100% bad against a 1% budget: the burn rate saturates near 100
    assert burn == pytest.approx(100.0, rel=0.05)
    assert st["ok"] is False
    # restart: counters reset to a zero block -> deltas clamp at zero
    tr.fold("r0", slo.empty_counters())
    st2 = slo.status_for(tr, cfg)
    assert st2["requests_total"] == 150
    # a replica leaving the fleet drops its fold baseline
    tr.forget_replica("r0")
    assert "r0" not in tr._last_seen


def test_slo_status_unconfigured_shape():
    st = slo.status_for(slo.BurnRateTracker(), None)
    assert st == {"configured": False}
    st = slo.status_for(None, slo.SLOConfig())
    assert st == {"configured": False}


def test_ledger_feeds_slo_snapshot_only_replica_side():
    mdefs.set_enabled(True)
    # proxy-side ledger (replica "-"): never folds (double-count guard)
    led = rl.start_request("http", "app", "dep")
    led.finish("ok")
    assert rl.slo_snapshot() == {}
    # replica-side ledger folds n/errors/latency buckets
    led = rl.start_request("replica", "app", "dep", "r0")
    led.note("ttft_s", 0.02)
    led.finish("ok", now=led.t0 + 0.05)
    led = rl.start_request("replica", "app", "dep", "r0")
    led.finish("rejected", "replica_saturated", now=led.t0 + 0.001)
    snap = rl.slo_snapshot()["app/dep"]
    assert snap["n"] == 2 and snap["errors"] == 1
    assert sum(snap["e2e"]) == 2 and sum(snap["ttft"]) == 1


# ----------------------------------------------------------------------
# engine: windowed TTFT decay + tick ring (CPU tiny model)
# ----------------------------------------------------------------------
jax = pytest.importorskip("jax")


@pytest.fixture(scope="module")
def model():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt(i, n=12):
    import numpy as np

    rng = np.random.RandomState(i)
    return [int(x) for x in rng.randint(1, 128, size=n)]


def test_storm_inflated_ttft_decays_within_window(model, monkeypatch):
    """Satellite regression: the shedding/autoscaling TTFT input is a
    WINDOWED percentile, so a storm's sky-high samples stop asserting
    pressure one window after the storm ends — the PR-10 idle-override
    workaround is retired because this decay makes it unreachable."""
    import time as _t

    from ray_tpu.serve.llm_engine import LlamaEngine

    monkeypatch.setenv("RT_SERVE_TTFT_WINDOW_S", "0.3")
    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=2, max_len=32, chunk=2)
    try:
        now = _t.monotonic()
        for _ in range(8):
            eng._ttft_samples.append((now, 9.0))  # storm aftermath
        assert eng._ttft_p90() == pytest.approx(9.0)
        deadline = _t.monotonic() + 5.0
        while eng._ttft_p90() > 0.0 and _t.monotonic() < deadline:
            _t.sleep(0.05)
        assert eng._ttft_p90() == 0.0  # decayed, not latched
        # ... so a fresh request against the idle engine is admitted
        # and served, never predicted-shed off the stale history
        fut = eng.submit(_prompt(0), 4, timeout_s=30.0)
        assert len(fut.result(timeout=60)) == 4
    finally:
        eng.shutdown()


def test_tick_ring_bounded_and_shaped(model, monkeypatch):
    monkeypatch.setenv("RT_ENGINE_TICK_RING", "4")
    from ray_tpu.serve.llm_engine import LlamaEngine

    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=2, max_len=32, chunk=2)
    try:
        futs = [eng.submit(_prompt(i), 4) for i in range(6)]
        for f in futs:
            f.result(timeout=60)
        ring = eng.stats()["tick_ring"]
        assert 0 < len(ring) <= 4  # capped at RT_ENGINE_TICK_RING
        last = ring[-1]
        assert {"seq", "admitted", "active", "queued", "live_tokens",
                "gather_blocks", "admit_s", "dispatch_s",
                "harvest_s"} <= set(last)
        assert ring == sorted(ring, key=lambda t: t["seq"])
        # what a tick used to copy from stats() every time is read
        # there (the dashboard's tick panel does): the route, the
        # overload counters, the free slots
        s = eng.stats()
        assert not {"kernel", "free_slots", "shed_expired",
                    "shed_predicted", "rejected_total"} & set(last)
        assert s["decode_kernel"] == "gather" and s["free_slots"] == 2
        assert (s["shed_expired"], s["shed_predicted"],
                s["rejected_total"]) == (0, 0, 0)
    finally:
        eng.shutdown()


def test_engine_hot_loop_zero_tickets_when_disabled(model, monkeypatch):
    """With RT_METRICS_ENABLED=0 and tracing off, the engine's submit
    path must never construct an EngineTicket — the per-request cost of
    a disabled telemetry plane is one None check."""
    from ray_tpu.serve.llm_engine import LlamaEngine

    assert not rl.enabled()
    calls = {"n": 0}
    real = rl.EngineTicket.__init__

    def _counting(self, *a, **k):
        calls["n"] += 1
        return real(self, *a, **k)

    monkeypatch.setattr(rl.EngineTicket, "__init__", _counting)
    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=2, max_len=32, chunk=2)
    try:
        futs = [eng.submit(_prompt(i), 4) for i in range(4)]
        for f in futs:
            f.result(timeout=60)
    finally:
        eng.shutdown()
    assert calls["n"] == 0
