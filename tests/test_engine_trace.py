"""The engine's own account of a request and of a tick (no cluster, CPU):
one lifecycle record per finished request in `stats()["request_ring"]`,
the engine-loop spans a `jax.profiler` session records on the host
plane, and the names the engine's programs and kernels lower under."""

import glob
import json
import pickle
import re
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from ray_tpu.metrics import metric_defs as mdefs
from ray_tpu.serve import request_ledger as rl

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.serve import llm_engine  # noqa: E402
from ray_tpu.serve.llm_engine import LlamaEngine  # noqa: E402

PHASES = ("queue_s", "prefill_dispatch_s", "first_token_s", "decode_s")


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture
def engine(model):
    eng = LlamaEngine(*model, slots=2, max_len=48, chunk=2, block_size=8)
    yield eng
    eng.shutdown()


def _prompt(i, n=12):
    rng = np.random.RandomState(i)
    return [int(x) for x in rng.randint(1, 128, size=n)]


def _serve(eng, n, new=5, first=0):
    futs = [eng.submit(_prompt(first + i), new) for i in range(n)]
    return [f.result(timeout=60) for f in futs]


# ----------------------------------------------------------------------
# A. the lifecycle ring
# ----------------------------------------------------------------------
def test_one_record_per_finished_request_and_the_phases_add_up(engine):
    stamps = []
    record = engine._record

    def spy(status, t_submit, t_done, *a, **k):
        stamps.append((t_submit, t_done))
        return record(status, t_submit, t_done, *a, **k)

    engine._record = spy
    outs = _serve(engine, 6)
    s = engine.stats()
    ring = s["request_ring"]
    assert [r["seq"] for r in ring] == list(range(1, 7))
    assert s["finished_total"] == 6 == len(stamps)
    for r, (t_submit, t_done), out in zip(ring, stamps, outs):
        assert r["status"] == "ok" and r["t_done"] == t_done
        assert all(r[k] >= 0.0 for k in PHASES)
        # queue + (admission -> first token) + decode is the whole of it
        assert (r["queue_s"] + (r["first_token_s"] - r["queue_s"])
                + r["decode_s"]) == pytest.approx(t_done - t_submit,
                                                  abs=1e-9)
        assert r["queue_s"] + r["prefill_dispatch_s"] <= r["first_token_s"]
        assert (r["tokens_in"], r["tokens_hit"]) == (12, 0)
        assert r["tokens_out"] == len(out) == 5
        # 5 tokens at chunk 2: the first chunk carries the prefill's
        # token and two more, the second the rest
        assert r["harvests"] == 2


def test_first_token_sample_is_the_records_first_token(engine):
    _serve(engine, 1)
    (_, ttft), = engine._ttft_samples
    assert ttft == engine.stats()["request_ring"][-1]["first_token_s"]


def test_a_prefix_hit_is_counted_in_the_record(engine):
    shared = _prompt(0, 24)
    for tail in (1, 2):
        engine.submit(shared + [tail], 3).result(timeout=60)
    first, second = engine.stats()["request_ring"]
    assert (first["tokens_in"], first["tokens_hit"]) == (25, 0)
    assert second["tokens_hit"] == 24  # three blocks of eight


def test_shed_and_refused_requests_are_recorded_with_their_status(engine):
    from ray_tpu.exceptions import BackPressureError, DeadlineExceededError

    # refused by submit(): the budget is already spent
    with pytest.raises(DeadlineExceededError):
        engine.submit(_prompt(0), 4, timeout_s=0.0).result(timeout=60)
    # shed at admission: the predictor says the budget cannot be met
    engine._ttft_samples.append((time.monotonic(), 9.0))
    with pytest.raises(DeadlineExceededError):
        engine.submit(_prompt(1), 4, timeout_s=1.0).result(timeout=60)
    engine._ttft_samples.clear()
    engine.begin_drain()
    with pytest.raises(BackPressureError):
        engine.submit(_prompt(2), 4).result(timeout=60)
    ring = engine.stats()["request_ring"]
    assert [r["status"] for r in ring] == [
        "expired_at_submit", "shed_predicted", "draining"]
    for r in ring:
        assert r["queue_s"] >= 0.0 and r["tokens_in"] == 12
        assert r["first_token_s"] is None and r["decode_s"] is None
        assert (r["harvests"], r["tokens_out"]) == (0, 0)
    assert engine.stats()["shed_total"] == 2


def test_the_ring_is_bounded_and_a_pickled_stats_stays_small(engine):
    assert llm_engine.REQUEST_RING == 448
    _serve(engine, 16, new=8)  # more ticks than their ring holds
    ok = dict(engine.stats()["request_ring"][-1])
    # the widest records there are: every phase a float
    with engine._ring_lock:
        for _ in range(600):
            engine._request_ring.append(dict(ok, t_done=time.time()))
    engine.begin_drain()
    for i in range(40):
        engine.submit(_prompt(i), 4)  # refused: one record each
    # ... the tick ring at its default, every tick starved (its widest
    # record); the account's 96 seconds, each as busy as a closed cell
    # on the chip shows one (a second of ticks, most of it waiting for
    # the device); the stalls kept, each three whole ticks
    with engine._lock:
        ticks = [dict(t) for t in engine._tick_ring if "host_gap_s" in t]
        assert len(engine._tick_ring) == 32 and len(ticks) >= 3
        engine._tick_ring.extend(dict(ticks[0]) for _ in range(32))
        for i in range(8):
            engine._stalls.append({
                "before": dict(ticks[0]), "tick": dict(ticks[1]),
                "after": dict(ticks[2]),
                "in_flight": ["prefill_packed_n2048", "decode_chunk_w128",
                              "prefill_packed_n512", "decode_chunk_w128"]})
        busy = (9, 1000123, 0, 21034, 33012, 4123, 850321, 91234, 151234,
                1212345, 31234, 2123, 9123, 3, 0, 9216, 8000, 4, 40, 30000,
                36864, 13, 35123, 21045)
        for i in range(200):
            engine._account.append((1790000000 + i,) + busy)
        # the launch account at its widest: 25 program names, each with
        # and without a profiler session, every count as large as a day
        # of a closed cell on the chip makes it
        for i in range(25):
            for traced in (False, True):
                engine._launch_account[
                    f"suffix_prefill_packed_n{2 ** (i % 12) * 8}_{i}",
                    traced] = [912345, 1212345, 2123456789, 3123456789,
                               4123456789012, 51234567, 61234567890,
                               7123.456789, 81234]
    s = engine.stats()
    launched = s["launch_account"]
    assert tuple(launched["fields"]) == llm_engine.LAUNCH_ACCOUNT_FIELDS
    assert len(launched["rows"]) >= 50
    assert all(len(r) == len(launched["fields"])
               and all(isinstance(v, int) for v in r[1:])
               for r in launched["rows"])
    assert len(pickle.dumps(launched)) < 6 * 1024
    assert len(s["request_ring"]) == 448
    assert s["request_ring"][-1]["seq"] == s["finished_total"] == 56
    acct = s["tick_account"]
    assert len(acct["rows"]) == llm_engine.ACCOUNT_SECONDS == 96
    assert all(len(r) == len(acct["fields"]) for r in acct["rows"])
    assert len(s["stalls"]) == llm_engine.STALLS_KEPT == 4
    # (63.6 KB here: the request ring 40.0, the account 8.3, the tick
    # ring 6.9, the launch account 4.8, the stalls 3.1)
    assert len(pickle.dumps(s)) < 64 * 1024


@pytest.fixture
def ledger_on():
    rl._reset_for_tests()
    mdefs.set_enabled(True)
    yield
    mdefs.set_enabled(False)
    rl._reset_for_tests()


def test_a_ticket_and_the_ring_carry_the_same_stamps(engine, ledger_on,
                                                     monkeypatch):
    led = rl.start_request("replica", "app", "dep", "r0")
    tickets = []
    real = rl.engine_ticket

    def keep(*a, **k):
        tickets.append(real(*a, **k))
        return tickets[-1]

    monkeypatch.setattr(rl, "engine_ticket", keep)
    with rl.use_ledger(led):
        fut = engine.submit(_prompt(3), 5)
    fut.result(timeout=60)
    (tk,), r = tickets, engine.stats()["request_ring"][-1]
    assert tk.t_done == r["t_done"]
    assert tk.t_admit - tk.t_submit == r["queue_s"]
    assert tk.t_prefill_done - tk.t_admit == r["prefill_dispatch_s"]
    assert tk.t_first - tk.t_submit == r["first_token_s"]
    assert tk.t_done - tk.t_first == r["decode_s"]
    assert led.notes["ttft_s"] == r["first_token_s"]
    assert led.notes["n_tokens"] == r["tokens_out"] == 5


def test_no_ticket_is_built_when_the_ledger_is_off(engine, monkeypatch):
    assert not rl.enabled()
    built = []
    real = rl.EngineTicket.__init__
    monkeypatch.setattr(
        rl.EngineTicket, "__init__",
        lambda self, *a, **k: (built.append(1), real(self, *a, **k))[1])
    _serve(engine, 3)
    assert not built and engine.stats()["finished_total"] == 3


def test_tick_records_carry_their_wall_time(engine):
    t0 = time.time()
    _serve(engine, 3)
    ring = engine.stats()["tick_ring"]
    walls = [t["t_wall"] for t in ring]
    assert walls == sorted(walls) and t0 <= walls[0] <= time.time()



@pytest.mark.parametrize("script", [
    [(12, 5)], [(12, 4)], [(12, 4), (7, 6)], [(3, 1)],
], ids=["whole-chunks", "mid-chunk", "two-rows", "prefill-token-only"])
def test_tick_records_count_the_row_steps_that_were_owed(engine, script):
    """`row_steps` is what a chunk computes (slots x chunk, 0 in a tick
    that dispatched none), `row_steps_live` what requests were waiting
    for: a request of n tokens owes n - 1 decode steps (its first token
    is the prefill's), wherever its budget ends in a chunk.  No chunk
    runs for a finished row's sake: a row's slot goes back at the
    dispatch of its last chunk, and the tick that harvests the last of
    them dispatches nothing."""
    futs = [engine.submit(_prompt(i, n), new)
            for i, (n, new) in enumerate(script)]
    for f in futs:
        f.result(timeout=60)
    ring = engine.stats()["tick_ring"]
    per_chunk = engine.slots * engine.chunk
    for t in ring:
        assert t["row_steps"] == (per_chunk if t["gather_blocks"] else 0)
        assert 0 <= t["row_steps_live"] <= t["row_steps"]
        # a row's cached tokens stop at its stop
        assert t["live_tokens"] <= sum(n + new - 1 for n, new in script)
    assert sum(t["row_steps_live"] for t in ring) == sum(
        new - 1 for _, new in script)
    # (a request of ONE token owes no step: the chunk that carries its
    # prefill's token to the host is all dead)
    dead = [t for t in ring if t["row_steps"] and not t["row_steps_live"]]
    assert len(dead) == (script == [(3, 1)])
    assert sum(t["handed_off"] for t in ring) == len(script)
    last = ring[-1]  # the harvest of the last chunk, and nothing else
    assert (last["row_steps"], last["active"], last["launches"]) == (0, 0, 0)
    assert last["seq"] == ring[-2]["seq"] == engine.stats()["ticks"]


# ----------------------------------------------------------------------
# A2. the tick's own account: phases, CPU, the host's gap, the account
#     by the second, the stalls
# ----------------------------------------------------------------------
IN_TICK = ("plan_s", "prefill_s", "dispatch_s", "device_wait_s",
           "harvest_host_s")


class _Reads:
    """What the engine thread's read of a chunk's tokens is made to
    cost: `delay` every time, `once` the next time only."""
    delay = once = 0.0


@pytest.fixture
def reads(monkeypatch):
    real, knob = np.asarray, _Reads()

    def slow(a, *args, **kw):
        if (threading.current_thread().name == "llm-engine"
                and isinstance(a, jax.Array)):
            nap, knob.once = knob.delay + knob.once, 0.0
            time.sleep(nap)
        return real(a, *args, **kw)

    monkeypatch.setattr(np, "asarray", slow)
    return knob


def test_a_ticks_phases_are_its_sums_and_its_wall(engine, reads):
    reads.delay = 0.02  # ticks of 20 ms: what no phase holds is small
    _serve(engine, 3, new=6)
    ring = engine.stats()["tick_ring"]
    assert len(ring) >= 4
    for t in ring:
        assert t["admit_s"] == t["plan_s"] + t["prefill_s"]
        assert t["harvest_s"] == t["device_wait_s"] + t["harvest_host_s"]
        assert t["dispatch_s"] >= 0 and t["wait_s"] >= 0
        assert sum(t[k] for k in IN_TICK) <= t["tick_s"]
    waited = [t for t in ring if t["device_wait_s"]]
    assert waited and all(t["device_wait_s"] >= 0.02 for t in waited)
    # the read's delay is in the wait for the device, not in the
    # host's half of the harvest
    assert all(t["harvest_host_s"] < 0.01 for t in waited)
    whole = sum(t["tick_s"] for t in waited)
    assert sum(t[k] for t in waited for k in IN_TICK) >= 0.99 * whole


def test_the_wait_before_a_tick_is_carried_into_it_and_costs_no_cpu(engine):
    _serve(engine, 1)
    time.sleep(0.3)  # idle: the loop is blocked on its wake-up
    _serve(engine, 1, first=1)
    ring = engine.stats()["tick_ring"]
    woke = max(ring, key=lambda t: t["wait_s"])
    assert 0.25 <= woke["wait_s"] <= 5.0 and woke["admitted"] == 1
    # the wait is outside the tick's wall, and slept
    assert woke["tick_s"] < woke["wait_s"]
    assert 0.0 <= woke["cpu_s"] < 0.2
    for t in ring:
        assert 0.0 <= t["cpu_s"] <= t["tick_s"] + t["wait_s"] + 0.05
        assert t["proc_cpu_s"] >= 0.0


def test_a_tick_that_finds_its_chunk_finished_is_starved(engine):
    width = engine._gather_width

    def finish_the_chunk_in_flight():
        if engine._pending_toks is not None:
            jax.block_until_ready(engine._pending_toks[0])
        return width()

    engine._gather_width = finish_the_chunk_in_flight
    _serve(engine, 2, new=8)
    ring = engine.stats()["tick_ring"]
    starved = [(a, b) for a, b in zip(ring, ring[1:]) if b["starved"]]
    assert len(starved) >= 3
    for before, t in starved:
        assert t["device_wait_s"] < llm_engine.STARVED_WAIT_S
        # from the read before to this tick's first program: the
        # harvest (and bookkeeping) of the tick before, this tick's
        # plan, its packing; the wait is no part of it
        assert t["gap_harvest_host_s"] >= before["harvest_host_s"]
        assert t["host_gap_s"] >= t["gap_harvest_host_s"] + t["plan_s"]
        assert t["host_gap_s"] <= (before["tick_s"] + t["tick_s"]
                                   - t["device_wait_s"])
    # a tick that dispatched nothing left the device nothing to idle for
    assert all(not t["starved"] for t in ring if not t["row_steps"]
               and not t.get("prefill_calls"))


def test_a_tick_that_waits_for_its_chunk_is_not_starved(engine, reads):
    reads.delay = 0.01
    _serve(engine, 2, new=8)
    ring = engine.stats()["tick_ring"]
    waited = [t for t in ring if t["device_wait_s"]]
    assert len(waited) >= 3
    assert all(not t["starved"] and "host_gap_s" not in t for t in waited)
    row = _account_total(engine.stats())
    assert row["starved"] == row["host_gap_us"] == 0


# the account's columns: PR 37's, then the launches' three
ACCOUNT_COLUMNS = llm_engine.ACCOUNT_FIELDS + llm_engine.ACCOUNT_LAUNCH_FIELDS


def _account_total(stats):
    """The account's columns summed over its seconds."""
    acct = stats["tick_account"]
    return dict(zip(acct["fields"], (sum(c) for c in zip(*acct["rows"]))))


def _summed(records):
    """What the account holds of `records`: a column a tick field."""
    out = dict.fromkeys(ACCOUNT_COLUMNS, 0)
    for t in records:
        out["ticks"] += 1
        for col in ACCOUNT_COLUMNS[2:]:
            key = col[:-3] + "_s" if col.endswith("_us") else col
            scale = 1e6 if col.endswith("_us") else 1
            out[col] += t.get(key, 0) * scale
        if "host_gap_s" in t:
            out["gap_plan_us"] += t["plan_s"] * 1e6
    return out


def test_the_account_is_the_ring_summed_by_the_second(model, monkeypatch):
    monkeypatch.setenv("RT_ENGINE_TICK_RING", "4096")
    eng = LlamaEngine(*model, slots=2, max_len=48, chunk=2, block_size=8)
    try:
        t_end = time.time() + 30  # far more than it takes
        n = 0
        # until ticks began in three wall seconds (a loaded host may
        # spend a whole second inside one compiling tick)
        while (len(eng.stats()["tick_account"]["rows"]) < 3
               and time.time() < t_end):
            _serve(eng, 2, first=n)
            n += 2
        s = eng.stats()
    finally:
        eng.shutdown()
    fields, rows = s["tick_account"]["fields"], s["tick_account"]["rows"]
    assert tuple(fields) == ACCOUNT_COLUMNS
    secs = [r[0] for r in rows]
    assert secs == sorted(set(secs)) and 3 <= len(secs) <= 4
    assert sum(r[1] for r in rows) == len(s["tick_ring"])
    # (`ticks` counts the chunks: a tick that only harvests has none)
    assert s["ticks"] == sum(bool(t["row_steps"]) for t in s["tick_ring"])
    for r in rows:
        got = dict(zip(fields, r))
        want = _summed([t for t in s["tick_ring"]
                        if int(t["t_wall"]) == got["sec"]])
        for col in fields[1:]:
            assert got[col] == pytest.approx(want[col], abs=1.0), col
        assert all(isinstance(v, int) for v in r)
    total = _account_total(s)
    assert total["prefill_rows"] == s["prefill_rows"] == n
    assert total["prefill_tokens"] == s["prefill_tokens"]
    assert total["prefill_padded_tokens"] == s["prefill_padded_tokens"]
    assert 0 < total["row_steps_live"] < total["row_steps"]
    # the pickled account is what the planes ship as JSON
    assert json.loads(json.dumps(s["tick_account"]))["rows"][0] == list(rows[0])


def test_the_account_keeps_the_last_96_seconds_that_had_a_tick(engine):
    tick = {"tick_s": 0.25, "plan_s": 0.01, "device_wait_s": 0.2,
            "row_steps": 4, "starved": True, "host_gap_s": 0.03,
            "gap_harvest_host_s": 0.01}
    with engine._lock:
        for sec in range(1000, 1400, 2):  # 200 seconds, two ticks each
            for _ in range(2):
                engine._account_add(dict(tick, t_wall=sec + 0.5))
    acct = engine.stats()["tick_account"]
    rows = [dict(zip(acct["fields"], r)) for r in acct["rows"]]
    # whatever the tick rate: seconds, not ticks, bound it
    assert [r["sec"] for r in rows] == list(range(1208, 1400, 2))
    assert all((r["ticks"], r["tick_us"], r["device_wait_us"], r["starved"],
                r["host_gap_us"], r["gap_harvest_host_us"],
                r["gap_plan_us"], r["row_steps"], r["wait_us"])
               == (2, 500000, 400000, 2, 60000, 20000, 20000, 8, 0)
               for r in rows)


def test_a_stalled_tick_is_kept_whole_with_its_neighbours(
        engine, reads, monkeypatch):
    monkeypatch.setattr(llm_engine, "STALL_MIN_S", 0.05)
    # compiled, and ticks enough for the EMA to forget the compiling
    _serve(engine, 12, new=9)
    assert engine.stats()["stalls"] == []
    assert engine.stats()["tick_ema_s"] < 0.01
    # the next read: of the request's first chunk, in its second tick
    # (an idle engine leaves no chunk in flight)
    reads.once = 0.4
    engine.submit(_prompt(99), 9).result(timeout=60)  # no prefix hit
    s = engine.stats()
    (stall,) = s["stalls"]
    tick = stall["tick"]
    assert tick["stalled"] and tick["device_wait_s"] >= 0.4
    assert tick["tick_s"] >= 0.4 and not tick["starved"]
    # slept, not computed: the CPU time says which
    assert tick["cpu_s"] < 0.2
    assert stall["before"]["seq"] == tick["seq"] - 1
    assert stall["after"]["seq"] == tick["seq"] + 1
    assert "stalled" not in stall["before"] and "stalled" not in stall["after"]
    # the tick before's programs, the chunk it waited for the last of
    # them, and what it had just launched itself
    assert [n.rstrip("0123456789") for n in stall["in_flight"]] == [
        "prefill_packed_n", "decode_chunk_w", "decode_chunk_w"]
    by_seq = {t["seq"]: t for t in s["tick_ring"]}
    assert by_seq[tick["seq"]] == tick  # the ring holds the same record
    assert _account_total(s)["stalled"] == 1


def test_a_tick_that_compiles_is_no_stall(engine, monkeypatch):
    monkeypatch.setattr(llm_engine, "STALL_MIN_S", 0.0)
    monkeypatch.setattr(llm_engine, "STALL_FACTOR", 1e-9)
    # every tick is "long" now; the first ticks of an engine compile the
    # programs they launch, and a later prompt of another width its own
    _serve(engine, 1)
    engine.submit(_prompt(1, 30), 10).result(timeout=60)
    s = engine.stats()
    compiled = [t for t in s["tick_ring"] if t.get("compiles")]
    assert len(compiled) >= 2
    assert all("stalled" not in t for t in compiled)
    kept = [st["tick"]["seq"] for st in s["stalls"]]
    assert kept and not set(kept) & {t["seq"] for t in compiled}
    assert len(s["stalls"]) <= llm_engine.STALLS_KEPT


def test_a_tick_that_prefilled_says_what_it_added(engine):
    _serve(engine, 5)
    s = engine.stats()
    for key in ("prefill_calls", "prefill_rows", "prefill_tokens",
                "prefill_padded_tokens"):
        assert sum(t.get(key, 0) for t in s["tick_ring"]) == s[key] > 0
    for t in s["tick_ring"]:
        assert ("prefill_calls" in t) == bool(t["prefill_s"])
        assert t.get("prefill_rows", 0) == t["admitted"]


# ----------------------------------------------------------------------
# A3. the launches' stamp: every program handed to the device
# ----------------------------------------------------------------------
class _StandIn:
    """A program whose CALL takes `nap` seconds to return (the host
    blocked: the device's queue was full), then runs the real one."""

    def __init__(self, fn, nap):
        self._fn, self.nap, self.__name__ = fn, nap, fn.__name__

    def _cache_size(self):
        return self._fn._cache_size()

    def __call__(self, *args):
        time.sleep(self.nap)
        return self._fn(*args)


def _burst(eng, reqs):
    """Every `(prompt, new)` into the queue at once, as ONE tick's
    admissions (submit() would wake the loop on the first of them)."""
    entries = [(p, n, Future(), time.time(), None, None) for p, n in reqs]
    with eng._wake:
        eng._queue.extend(entries)
        eng._wake.notify()
    return [e[2] for e in entries]


def _launched(stats):
    """`launch_account` as {(program, traced): {field: value}}."""
    acct = stats["launch_account"]
    return {(r[0], r[1]): dict(zip(acct["fields"][2:], r[2:]))
            for r in acct["rows"]}


@pytest.mark.parametrize("nap,blocks", [
    (4 * llm_engine.LAUNCH_BLOCKED_S, True),
    (llm_engine.LAUNCH_BLOCKED_S / 5, False),
], ids=["a-call-that-blocks", "a-call-that-returns"])
def test_a_launch_that_blocks_is_told_from_host_work(engine, reads, nap,
                                                     blocks):
    _serve(engine, 2, new=8)  # compile outside what is looked at
    chunk_for = engine._chunk_step_for
    engine._chunk_step_for = lambda W: _StandIn(chunk_for(W), nap)
    reads.delay = 0.02  # ticks of 20 ms: what no phase holds is small
    seq = engine.stats()["ticks"]
    _serve(engine, 2, new=8, first=2)
    s = engine.stats()
    ring = [t for t in s["tick_ring"] if t["seq"] > seq and t["row_steps"]]
    assert len(ring) >= 4 and not any(t.get("compiles") for t in ring)
    for t in ring:
        # the chunk's call, and a packed prefill's where one was admitted
        assert t["launches"] == 1 + t.get("prefill_calls", 0)
        assert nap <= t["launch_s"] <= t["prefill_s"] + t["dispatch_s"]
        if blocks:
            assert nap <= t["launch_blocked_s"] <= t["launch_s"]
            # the host's own work of the tick is what is left
            assert t["dispatch_s"] - t["launch_blocked_s"] < nap
        else:
            assert t["launch_blocked_s"] == 0
    # parts of `prefill_s` / `dispatch_s`, not phases beside them: the
    # five phases still are the tick's wall
    whole = sum(t["tick_s"] for t in ring)
    assert sum(t[k] for t in ring for k in IN_TICK) >= 0.99 * whole
    assert sum(t[k] for t in ring for k in IN_TICK) <= whole
    total = _account_total(s)
    for col, key in (("launches", "launches"), ("launch_us", "launch_s"),
                     ("launch_blocked_us", "launch_blocked_s")):
        scale = 1e6 if col.endswith("_us") else 1
        assert total[col] == pytest.approx(
            sum(t[key] for t in s["tick_ring"]) * scale, abs=len(s["tick_ring"]))
    by = _launched(s)
    chunks = [v for (name, _), v in by.items()
              if name.startswith("decode_chunk")]
    assert sum(v["blocked"] for v in chunks) == (
        sum(1 for t in s["tick_ring"] if t["launch_blocked_s"])
        if blocks else 0)
    # appended: a row of before the stamp is a prefix of a row of now
    assert s["tick_account"]["fields"][-3:] == (
        "launches", "launch_us", "launch_blocked_us")


def test_a_launch_that_compiles_is_not_blocked(model):
    eng = LlamaEngine(*model, slots=2, max_len=48, chunk=2, block_size=8)
    try:
        _serve(eng, 1)
        s = eng.stats()
    finally:
        eng.shutdown()
    compiled = [t for t in s["tick_ring"] if t.get("compiles")]
    assert compiled and all(t["launch_s"] > llm_engine.LAUNCH_BLOCKED_S
                            for t in compiled)
    assert all(t["launch_blocked_s"] == 0 for t in compiled)


def _pairs(*parts):
    """The formula, spelled out: a query at position p attends p + 1
    keys."""
    return sum(p + 1 for lo, hi in parts for p in range(lo, hi))


def test_a_launch_says_what_it_holds(model):
    """Real programs of the dense-prefix model: a packed pack of two
    prompts, then a prefix hit's suffix (two launches: the prefill and
    the write), then the chunks that decode them."""
    eng = LlamaEngine(*model, slots=2, max_len=48, chunk=2, block_size=8,
                      prefix_cache=True)
    try:
        shared = _prompt(7, 16)
        f0, f1 = _burst(eng, [(_prompt(0, 12), 4), (_prompt(1, 9), 4)])
        f0.result(timeout=60), f1.result(timeout=60)
        eng.submit(shared + _prompt(8, 5), 3).result(timeout=60)
        eng.submit(shared + _prompt(9, 7), 3).result(timeout=60)  # a hit
        s = eng.stats()
    finally:
        eng.shutdown()
    by = _launched(s)
    assert {t for _, t in by} == {0}  # no profiler session
    packed = by["prefill_packed_n48", 0]
    # the pack of two, then the first long prompt alone (a miss)
    assert packed["launches"] == 2 and packed["rows"] == 3
    assert packed["tokens"] == 12 + 9 + 21
    assert packed["padded_tokens"] == 2 * 48
    assert packed["attended_pairs"] == _pairs((0, 12), (0, 9), (0, 21))
    (suffix,) = [v for (n, _), v in by.items()
                 if n.startswith("suffix_prefill_")]
    (write,) = [v for (n, _), v in by.items() if n.startswith("kv_write_")]
    # the hit: 16 tokens cached, 7 prefilled behind them
    assert s["prefix_hit_tokens"] == 16
    assert (suffix["launches"], suffix["rows"], suffix["tokens"],
            suffix["padded_tokens"]) == (1, 1, 7, 8)
    assert suffix["attended_pairs"] == _pairs((16, 23)) == 7 * 16 + 28
    assert (write["tokens"], write["attended_pairs"]) == (7, 0)
    chunks = [v for (n, _), v in by.items() if n.startswith("decode_chunk")]
    assert sum(v["launches"] for v in chunks) == s["ticks"]
    assert all(v["padded_tokens"] == v["rows"] == v["tokens"] == 0
               for v in chunks)
    # a chunk's live rows, and the tokens they attend at its first step
    assert sum(v["rows_live"] for v in chunks) > 0
    assert sum(v["live_tokens"] for v in chunks) >= sum(
        v["rows_live"] for v in chunks) * 10
    # the two accounts count the same launches
    assert sum(v["launches"] for v in by.values()) == _account_total(
        s)["launches"]


class _Recorded(Exception):
    pass


@pytest.mark.parametrize("run,parts", [
    # a packed-suffix pack: a miss, a hit's suffix and a long prompt's
    # second chunk, each behind its own `lo`
    ("suffixes", [(0, 11), (16, 29), (32, 40)]),
    # a chunk that carries the slot's per-slot leaves
    ("state_chunk", [(24, 37)]),
    ("suffix_chunk", [(8, 21)]),
], ids=["packed-suffix-pack", "state-chunk", "suffix-chunk"])
def test_every_admission_family_counts_its_attended_pairs(engine, run, parts):
    """The three callers a dense-prefix model's traffic does not reach
    (`_run_suffixes`: a model that packs its suffixes; `_run_state_chunk`:
    one that carries per-slot leaves; `_run_suffix_chunk` at a chosen
    `lo`), driven on hand-made plans with the launch itself recorded."""
    held = []

    def record(fn, *args, **kw):
        held.append((fn.__name__, kw))
        raise _Recorded

    engine._launch = record
    engine._admitting = engine._prefilled = lambda plans: None
    engine._chunk_prefill_for = engine._prefill_packed_for = (
        lambda N: _StandIn(len, 0))  # (no such program of this model)
    req = {"stop": 44}
    # `lo` tokens in shared blocks, the rest of the sequence's six its own
    plans = [llm_engine._Plan(req, i, list(range(1, hi + 1)),
                              list(range(1, 1 + lo // 8)),
                              list(range(10, 16 - lo // 8)))
             for i, (lo, hi) in enumerate(parts)]
    with pytest.raises(_Recorded):
        if run == "suffixes":
            engine._run_suffixes(48, [llm_engine._Part(p, lo, hi) for p,
                                      (lo, hi) in zip(plans, parts)])
        elif run == "state_chunk":
            engine._run_state_chunk(plans[0], *parts[0], 1, 2, N=16)
        else:
            engine._run_suffix_chunk(plans[0], *parts[0], 0, 1)
    (name, kw), = held
    real = sum(hi - lo for lo, hi in parts)
    assert (kw["rows"], kw["tokens"]) == (len(parts), real)
    assert kw["N"] == {"suffixes": 48, "state_chunk": 16,
                       "suffix_chunk": 16}[run]
    assert kw["attended_pairs"] == _pairs(*parts)
    assert kw["attended_pairs"] == sum(
        (hi - lo) * lo + (hi - lo) * (hi - lo + 1) // 2 for lo, hi in parts)


def test_the_stamp_costs_microseconds_a_launch(engine):
    """What `_launch` adds to a program's own call, outside a profiler
    session: two clock reads, a span that is a no-op, a record."""
    class Nothing:
        __name__ = "nothing"

        @staticmethod
        def _cache_size():
            return 1

        def __call__(self, *args):
            return None

    fn, n = Nothing(), 20_000
    held = dict(N=2048, rows=3, tokens=1900, attended_pairs=1234567)
    t0 = time.perf_counter()
    for _ in range(n):
        fn(1, 2, 3)
    bare = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        engine._launch(fn, 1, 2, 3, **held)
    stamped = time.perf_counter() - t0
    engine._launches.clear()
    per_launch_us = 1e6 * (stamped - bare) / n
    print(f"the launch stamp: {per_launch_us:.2f} us a launch")
    assert per_launch_us < 100  # (3-6 us here; a program is 10-230 ms)


# ----------------------------------------------------------------------
# B. the engine-loop spans, under a profiler session
# ----------------------------------------------------------------------
def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    assert len(host) == 1
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for line in host[0].lines for e in line.events
            if e.name.startswith("engine.")]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_profiler_session_records_the_loops_spans_nested(engine, tmp_path):
    _serve(engine, 2)  # compile outside the session
    t0 = time.time_ns()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(engine, 4, first=10)  # unshared prompts: no prefix hit
    finally:
        jax.profiler.stop_trace()
    t1 = time.time_ns()
    spans = _host_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert {"engine.tick", "engine.admit", "engine.plan", "engine.prefill",
            "engine.dispatch", "engine.harvest", "engine.device_wait",
            "engine.harvest_host"} <= set(by)
    ticks = by["engine.tick"]
    for _, _, _, st in ticks:
        assert {"seq", "active", "admitted", "wall_ns"} <= set(st)
        assert t0 <= st["wall_ns"] <= t1  # the anchor to the wall clock
    assert sum(st["admitted"] for *_, st in ticks) == 4
    # one span a packed program: the four requests, 12 real tokens
    # each, in programs of 48 (`max_len` in whole blocks) that hold as
    # many rows as a tick admitted (at most the two slots)
    packs = [st for *_, st in by["engine.prefill"]]
    assert 2 <= len(packs) <= 4
    assert all(st["N"] == 48 and st["rows"] in (1, 2)
               and st["tokens"] == 12 * st["rows"] for st in packs)
    assert sum(st["rows"] for st in packs) == 4
    assert all(st["W"] >= 1 for *_, st in by["engine.dispatch"])

    def inside(inner, outers):
        return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)

    # a tick in flight when the session starts or stops is not recorded,
    # though the phases it ran inside the session are: look between the
    # first recorded tick's start and the last one's end
    lo, hi = min(t[1] for t in ticks), max(t[2] for t in ticks)
    for name in ("engine.admit", "engine.dispatch", "engine.harvest"):
        whole = [s for s in by[name] if lo <= s[1] and s[2] <= hi]
        assert whole and all(inside(s, ticks) for s in whole), name
    assert all(inside(s, by["engine.admit"]) for s in by["engine.prefill"])
    # the two halves of admission and of the harvest, each in its whole
    for inner, outer in (("engine.plan", "engine.admit"),
                         ("engine.device_wait", "engine.harvest"),
                         ("engine.harvest_host", "engine.harvest")):
        whole = [s for s in by[inner] if lo <= s[1] and s[2] <= hi]
        assert whole and all(inside(s, by[outer]) for s in whole), inner
    for admit in by["engine.admit"]:  # the plan first, then the programs
        plans = [s for s in by["engine.plan"] if inside(s, [admit])]
        packs = [s for s in by["engine.prefill"] if inside(s, [admit])]
        assert all(s[2] <= p[1] for s in plans for p in packs)
    # blocked on the wake-up: outside every tick
    assert not any(inside(s, ticks) for s in by.get("engine.wait", []))
    # one stamp, two surfaces: a recorded tick is its ring record (the
    # anchor `wall_ns` is the record's `t_wall`), and what its spans
    # measure is what its fields hold
    ring = {t["t_wall"]: t for t in engine.stats()["tick_ring"]}
    matched = 0
    for _, a, b, st in ticks:
        rec = ring.get(st["wall_ns"] * 1e-9)
        if rec is None:
            continue
        matched += 1
        for phase in ("plan", "prefill", "dispatch", "device_wait",
                      "harvest_host"):
            spanned = sum(s[2] - s[1] for s in by.get("engine." + phase, [])
                          if a <= s[1] and s[2] <= b) * 1e-9
            assert rec[phase + "_s"] <= spanned + 1e-6, phase
            assert spanned - rec[phase + "_s"] < 5e-4, phase
    assert matched >= 3


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_hand_off_is_on_the_tick_record_and_the_dispatch_span(
        engine, tmp_path):
    """A request of 5 tokens at a chunk of 2 ends in its second chunk:
    the tick that dispatches it says `handed_off` 1, on its record and
    on its `engine.dispatch` span, and still counts the request
    `active` (it is, until the next tick's harvest); the slot is free
    from that dispatch on."""
    _serve(engine, 1)  # compile outside the session
    seq0 = engine.stats()["ticks"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(engine, 1, first=20)
        s = engine.stats()
    finally:
        jax.profiler.stop_trace()
    first, second, third = [t for t in s["tick_ring"] if t["seq"] > seq0][:3]
    assert (first["handed_off"], first["active"]) == (0, 1)
    assert (second["handed_off"], second["active"]) == (1, 1)
    assert (third["handed_off"], third["active"]) == (0, 0)
    assert third["seq"] == second["seq"] and not third["row_steps"]
    assert s["handoffs_total"] == s["finished_total"] == 2
    assert (s["active"], s["free_slots"]) == (0, 2)
    spans = [st for name, _, _, st in _host_spans(tmp_path)
             if name == "engine.dispatch"]
    assert [st["handed_off"] for st in spans] == [0, 1]
    # the tick after, which only harvests, says who it still waits for
    ticks = [st for name, _, _, st in _host_spans(tmp_path)
             if name == "engine.tick"]
    assert [st["active"] for st in ticks][-1] == 1


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_profiler_session_splits_the_launch_account(engine, tmp_path):
    """`launch_account`'s `traced` rows are what the programs launched
    inside the LATEST profiler session held: what a device trace's
    `jit_<program>` calls are set against."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    _serve(engine, 2)  # compile outside the sessions
    before = _launched(engine.stats())
    assert before and {traced for _, traced in before} == {0}

    def session(to, n, first):
        jax.profiler.start_trace(str(to))
        try:
            assert jax.profiler.TraceAnnotation.is_enabled()
            _serve(engine, n, first=first)  # unshared prompts
            time.sleep(0.05)  # the loop closes its last tick
        finally:
            jax.profiler.stop_trace()
        return _launched(engine.stats())

    first = session(tmp_path / "a", 4, 10)
    traced = {n: v for (n, t), v in first.items() if t}
    packed = traced["prefill_packed_n48"]
    assert (packed["rows"], packed["tokens"]) == (4, 48)
    assert packed["attended_pairs"] == 4 * (12 * 13 // 2)
    assert packed["padded_tokens"] == 48 * packed["launches"]
    # the untraced rows stand as they were: nothing is counted twice
    assert {k: v for k, v in first.items() if not k[1]} == before
    # the spans say the same, launch by launch, each inside the phase
    # that launched it
    spans = _host_spans(tmp_path / "a")
    launches = [s for s in spans if s[0] == "engine.launch"]
    outer = [s for s in spans
             if s[0] in ("engine.prefill", "engine.dispatch")]
    assert all(any(o[1] <= s[1] and s[2] <= o[2] for o in outer)
               for s in launches)
    by_program = {}
    for *_, st in launches:
        by_program.setdefault(st["program"], []).append(st)
    # (a tick in flight at either edge: its span may be cut, its launch
    # is counted by where the launch itself fell)
    for name, row in traced.items():
        assert abs(len(by_program.get(name, ())) - row["launches"]) <= 1
    assert sum(st["attended_pairs"] for st in by_program[
        "prefill_packed_n48"]) == packed["attended_pairs"]
    chunks = [st for name, sts in by_program.items()
              if name.startswith("decode_chunk") for st in sts]
    # (finish detection lags a chunk: a row's last chunk may owe nothing)
    assert all(st["N"] == 0 and st["live_tokens"] >= 12 * st["rows_live"]
               for st in chunks) and any(st["rows_live"] for st in chunks)
    # outside a session again: the traced rows keep the session's
    _serve(engine, 2, first=20)
    after = _launched(engine.stats())
    assert {k: v for k, v in after.items() if k[1]} == {
        k: v for k, v in first.items() if k[1]}
    assert after["prefill_packed_n48", 0]["rows"] == 2 + 2
    # a second session starts its rows anew
    second = session(tmp_path / "b", 2, 30)
    packed = second["prefill_packed_n48", 1]
    assert (packed["rows"], packed["tokens"]) == (2, 24)
    assert second["prefill_packed_n48", 0]["rows"] == 4


# ----------------------------------------------------------------------
# C. names: programs and kernels
# ----------------------------------------------------------------------
def test_the_jitted_families_lower_under_their_names(engine):
    cfg, e = engine.cfg, engine
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    i32 = jnp.int32
    tables = jnp.zeros((e.slots, 2), i32)
    chunk = e._chunk_step_for(2).lower(
        e.params, *e._cache, tables, e._tok, e._pos, e._stop)
    assert "@jit_decode_chunk_w2" in chunk.as_text()
    packed = e._prefill_packed_for(48).lower(
        e.params, *e._cache, *e._pack_arrays(48, []),
        e._pos, e._tok, e._stop)
    assert "@jit_prefill_packed_n48" in packed.as_text()
    prefill = e._prefill_for(16).lower(e.params, jnp.zeros((1, 16), i32))
    assert "@jit_prefill_b16" in prefill.as_text()
    kv1 = jnp.zeros((L, 1, 16, KV, hd), cfg.dtype)
    write = e._write_blocks_for(16, 2).lower(
        *e._cache, kv1, kv1, jnp.zeros((2,), i32),
        jnp.asarray(0, i32), jnp.asarray(12, i32), jnp.asarray(1, i32),
        e._pos, e._tok)
    assert "@jit_kv_write_t16_n2" in write.as_text()
    suffix = e._suffix_prefill_for(8, 2).lower(
        e.params, *e._cache, jnp.zeros((1, 8), i32),
        jnp.zeros((2,), i32), jnp.asarray(16, i32))
    assert "@jit_suffix_prefill_s8_p2" in suffix.as_text()


def _tpu_lowering(fn, *shapes):
    """Lowered for the TPU from shapes (Mosaic is part of jaxlib; no
    chip and no libtpu needed), with the ops' name stacks in it."""
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def test_the_paged_kernels_lower_under_their_names():
    from ray_tpu.ops import paged_attention as pa

    L, NB, BS, KV, HD, B, W, H = 2, 16, 16, 8, 128, 8, 2, 32

    def S(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    def step(q, kp, vp, kn, vn, tables, pos):
        kp, vp = pa.paged_kv_append(kp, vp, kn, vn, tables, pos, 0)
        return pa.paged_decode_attention(q, kp, vp, tables, pos, 0)

    text = _tpu_lowering(
        step, S(B, H, HD), S(L, NB, BS, KV, HD), S(L, NB, BS, KV, HD),
        S(B, KV, HD), S(B, KV, HD), S(B, W, dtype=jnp.int32),
        S(B, dtype=jnp.int32))
    # the kernel's `name` is the scope its pallas_call lowers under
    assert "/paged_kv_append/pallas_call" in text
    assert "/paged_decode_attention/pallas_call" in text


@pytest.mark.parametrize("T,block,names", [
    (512, 512, ("flash_fwd", "flash_bwd_fused")),
    (512, 256, ("flash_fwd", "flash_bwd_dq_grouped", "flash_bwd_dkv_grouped")),
])
def test_the_flash_kernels_lower_under_their_names(T, block, names):
    from ray_tpu.ops import flash_attention

    qkv = jax.ShapeDtypeStruct((1, T, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block).sum()

    text = _tpu_lowering(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    for name in names:
        # under grad the scope is wrapped: `jvp(flash_fwd)/pallas_call`
        assert re.search(rf"[/(]{name}\)*/pallas_call", text), name


def test_the_models_block_parts_are_named_scopes(model):
    from ray_tpu.models import gpt2

    cfg, params = model
    text = jax.jit(lambda p, t: llama.forward(cfg, p, t)).lower(
        params, jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
    for part in ("embed", "attn", "attn/norm", "mlp", "mlp/norm", "lm_head"):
        assert f"/{part}/" in text, part
    gcfg = gpt2.GPT2Config.tiny()
    gparams = gpt2.init_params(gcfg, jax.random.PRNGKey(0))
    text = jax.jit(lambda p, t: gpt2.forward(gcfg, p, t)).lower(
        gparams, jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
    for part in ("embed", "attn", "attn/norm", "mlp", "mlp/norm", "lm_head"):
        assert f"/{part}/" in text, part
