"""Unit coverage for the fault-tolerance primitives: jittered backoff,
retry budgets, per-address circuit breakers, and deadline wire
propagation (docs/fault_tolerance.md; reference analogs: the retry
pacing of `ray_config_def.h` task_retry_delay_ms, Finagle-style retry
budgets, gRPC deadline propagation).

Everything here is deterministic and cluster-free — the end-to-end
behaviors under injected faults live in test_chaos*.py.
"""

import random
import time

import pytest

from ray_tpu import exceptions as exc
from ray_tpu.core import rpc, wire
from ray_tpu.core.retry import RetryBudget, backoff_delay_s


# ----------------------------------------------------------------------
# backoff schedule
# ----------------------------------------------------------------------
def test_backoff_full_jitter_bounds():
    rng = random.Random(42)
    for attempt in range(12):
        for _ in range(50):
            d = backoff_delay_s(attempt, base_s=0.05, cap_s=5.0, rng=rng)
            assert 0.0 <= d <= min(5.0, 0.05 * 2**attempt)


def test_backoff_floor_is_legacy_retry_delay():
    rng = random.Random(0)
    # floor above the jitter range: every delay lands exactly on it
    for _ in range(20):
        assert backoff_delay_s(0, base_s=0.01, cap_s=5.0,
                               floor_s=0.5, rng=rng) >= 0.5


def test_backoff_cap_bounds_late_attempts():
    rng = random.Random(1)
    # attempt 60 must not overflow or exceed the cap
    for _ in range(20):
        assert backoff_delay_s(60, base_s=0.05, cap_s=2.0, rng=rng) <= 2.0


def test_backoff_is_deterministic_under_seed():
    a = [backoff_delay_s(i, base_s=0.05, cap_s=5.0,
                         rng=random.Random(7)) for i in range(5)]
    b = [backoff_delay_s(i, base_s=0.05, cap_s=5.0,
                         rng=random.Random(7)) for i in range(5)]
    assert a == b


# ----------------------------------------------------------------------
# retry budget
# ----------------------------------------------------------------------
def test_retry_budget_drains_and_refills():
    budget = RetryBudget(cap=2.0, refill=0.5)
    assert budget.try_acquire()
    assert budget.try_acquire()
    assert not budget.try_acquire()  # drained: fail fast
    assert budget.retries_granted == 2
    budget.record_success()  # +0.5: still below one token
    assert not budget.try_acquire()
    budget.record_success()  # 1.0 token
    assert budget.try_acquire()


def test_retry_budget_caps_at_bucket_size():
    budget = RetryBudget(cap=3.0, refill=1.0)
    for _ in range(100):
        budget.record_success()
    assert budget.tokens == 3.0


# ----------------------------------------------------------------------
# circuit breaker
# ----------------------------------------------------------------------
def test_breaker_opens_after_consecutive_failures():
    br = rpc.CircuitBreaker(failure_threshold=3, cooldown_s=60.0)
    for _ in range(2):
        br.record_failure()
    assert br.allow() and br.state == rpc.CircuitBreaker.CLOSED
    br.record_success()  # success resets the consecutive count
    for _ in range(2):
        br.record_failure()
    assert br.allow()
    br.record_failure()  # third consecutive: open
    assert br.state == rpc.CircuitBreaker.OPEN
    assert not br.allow()


def test_breaker_half_open_probe_and_recovery():
    br = rpc.CircuitBreaker(failure_threshold=1, cooldown_s=0.05)
    br.record_failure()
    assert not br.allow()
    time.sleep(0.06)
    assert br.allow()  # cooldown elapsed: probe admitted
    assert br.state == rpc.CircuitBreaker.HALF_OPEN
    br.record_success()
    assert br.state == rpc.CircuitBreaker.CLOSED


def test_breaker_half_open_failure_reopens():
    br = rpc.CircuitBreaker(failure_threshold=1, cooldown_s=0.05)
    br.record_failure()
    time.sleep(0.06)
    assert br.allow()
    br.record_failure()  # probe failed: back to open, fresh cooldown
    assert br.state == rpc.CircuitBreaker.OPEN
    assert not br.allow()


def test_reset_breakers_resets_cached_objects_in_place():
    """Routers cache breaker objects in replica tables; a full reset
    (rt.shutdown) must close those too, not only clear the board —
    else a stale open breaker ejects the next session's healthy peer."""
    rpc.reset_breakers()
    br = rpc.breaker_for("test:cached")
    for _ in range(br.failure_threshold):
        br.record_failure()
    assert not br.allow()
    rpc.reset_breakers()
    assert br.allow() and br.state == rpc.CircuitBreaker.CLOSED


def test_breaker_board_bounded_under_churn():
    """Peers that die before ever connecting (a lease socket whose
    worker crashed pre-accept) have no close event to drop their
    breaker; the board caps itself by evicting least-recently-touched
    CLOSED breakers — open ones (active ejection state) survive."""
    rpc.reset_breakers()
    try:
        held_open = rpc.breaker_for("test:churn-open")
        for _ in range(held_open.failure_threshold):
            held_open.record_failure()
        assert not held_open.allow()
        for i in range(rpc._BREAKER_BOARD_CAP + 50):
            rpc.breaker_for(f"test:churn-{i}")
        with rpc._breakers_lock:
            assert len(rpc._breakers) <= rpc._BREAKER_BOARD_CAP
            assert rpc._breakers.get("test:churn-open") is held_open
    finally:
        rpc.reset_breakers()


def test_multiplex_affinity_stable_across_breaker_flap():
    """Opening one replica's breaker diverts only the models resident
    there; every other model keeps its replica (no cluster-wide model
    reload when a breaker flaps)."""
    from ray_tpu.serve.router import Router

    rpc.reset_breakers()
    from ray_tpu.serve.multiplex import MODEL_ID_KWARG

    router = Router("dep", "app")
    router._install_table({
        "version": 1, "incarnation": "i1",
        "replicas": {"r1": (None, 100), "r2": (None, 100),
                     "r3": (None, 100)},
    })
    try:
        keys = [f"model-{i}" for i in range(40)]

        def _assign():
            out = {}
            for k in keys:
                info = router._try_pick(affinity_key=k)
                out[k] = info.replica_id
                info.local_inflight -= 1
            return out

        before = _assign()
        victim = before[keys[0]]
        br = rpc.breaker_for(router._breaker_key(victim))
        for _ in range(br.failure_threshold):
            br.record_failure()
        after = _assign()
        for k in keys:
            if before[k] == victim:
                assert after[k] != victim, "open breaker must divert"
            else:
                assert after[k] == before[k], \
                    "unaffected models must stay resident"
    finally:
        rpc.reset_breakers()


def test_breaker_board_is_per_address():
    rpc.reset_breakers()
    a = rpc.breaker_for("test:addr-a")
    b = rpc.breaker_for("test:addr-b")
    assert a is not b
    assert rpc.breaker_for("test:addr-a") is a
    for _ in range(a.failure_threshold):
        a.record_failure()
    assert not a.allow() and b.allow()
    rpc.reset_breakers()


# ----------------------------------------------------------------------
# deadline wire propagation
# ----------------------------------------------------------------------
def _spec(**kw):
    from ray_tpu.core.ids import JobID, TaskID
    from ray_tpu.core.task_spec import Resources, TaskSpec

    return TaskSpec(
        task_id=TaskID.for_job(JobID.random()),
        function_id=b"f" * 16, function_blob=None, args=[], kwargs={},
        num_returns=1, owner=("n", "w"), resources=Resources(), **kw,
    )


def test_deadline_travels_as_remaining_budget():
    wire.register_core_schemas()
    spec = _spec(deadline_s=time.monotonic() + 10.0)
    out = wire.decode(wire.encode(spec))
    # re-anchored on the decoder's clock, shrunk by transit time only
    assert out.deadline_s is not None
    assert 9.0 < out.deadline_remaining_s <= 10.0
    # a second hop shrinks it again, never grows it
    out2 = wire.decode(wire.encode(out))
    assert out2.deadline_remaining_s <= 10.0


def test_no_deadline_roundtrips_as_none():
    wire.register_core_schemas()
    out = wire.decode(wire.encode(_spec()))
    assert out.deadline_s is None
    assert not out.deadline_expired()


def test_deadline_expired_predicate():
    assert _spec(deadline_s=time.monotonic() - 0.1).deadline_expired()
    assert not _spec(deadline_s=time.monotonic() + 60).deadline_expired()


# ----------------------------------------------------------------------
# exception hierarchy
# ----------------------------------------------------------------------
def test_deadline_error_is_a_get_timeout_error():
    """Existing `except GetTimeoutError` call sites keep working."""
    err = exc.DeadlineExceededError("late", timeout_s=2.0)
    assert isinstance(err, exc.GetTimeoutError)
    assert isinstance(err, TimeoutError)
    assert err.timeout_s == 2.0


def test_get_timeout_error_carries_context_through_pickle():
    import pickle

    err = exc.GetTimeoutError("timed out", timeout_s=1.5, object_id=b"oid")
    out = pickle.loads(pickle.dumps(err))
    assert out.timeout_s == 1.5 and out.object_id == b"oid"


def test_router_assignment_expiry_is_deadline_exceeded():
    """A handle-level deadline that expires while NO replica is
    available must surface as the documented DeadlineExceededError;
    the legacy default wait keeps its plain TimeoutError."""
    from ray_tpu.serve.router import Router

    router = Router("dep", "app")
    router._install_table({
        "version": 1, "incarnation": "i1", "replicas": {},
    })
    router._refresh = lambda force=False: None  # no controller here
    expired = time.monotonic() - 0.01
    with pytest.raises(exc.DeadlineExceededError):
        router.assign_request("m", (), {}, deadline_s=expired)
    with pytest.raises(TimeoutError) as ei:
        router.assign_request("m", (), {}, timeout_s=0.01)
    assert not isinstance(ei.value, exc.DeadlineExceededError)


def test_timeout_s_option_validation():
    import ray_tpu as rt

    f = rt.remote(lambda: None)
    with pytest.raises(ValueError, match="timeout_s"):
        f.options(timeout_s=0)
    with pytest.raises(ValueError, match="timeout_s"):
        f.options(timeout_s=-1.0)
    f.options(timeout_s=2.5)  # valid: no error

    # serve handles share the same validator (one error contract)
    from ray_tpu.serve.handle import DeploymentHandle

    h = DeploymentHandle("dep")
    with pytest.raises(ValueError, match="timeout_s"):
        h.options(timeout_s=0)
    with pytest.raises(ValueError, match="timeout_s"):
        h.options(timeout_s="nope")
    h.options(timeout_s=2.5)  # valid: no error
