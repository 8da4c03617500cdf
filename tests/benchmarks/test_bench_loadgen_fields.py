"""`request_fields` (PR 54): what a traffic file may say of a request
beside its lengths.  The fields are the mix's own draw, they ride with
a request's lengths, they reach the body and the records; and a mix
WITHOUT the key schedules and sends what it did before the key existed,
held against literals taken from the parent's tree (PR 53, commit
51eedf4: `git archive`, the same calls)."""

import asyncio
import hashlib
import json
import threading
from collections import Counter

import pytest

from benchmarks import cell, loadgen, manifest

SEED = 5400000101          # above 2**31: the driver's are
STEPS = {"choices": [1, 2, 4, 8], "weights": [4, 2, 1, 1]}
FIELDS = {"steps": STEPS, "threshold": {"choices": [0.9, 0.95]},
          "tag": {"fixed": "b32"}}
OPEN = manifest.traffic("chat_open")
CLOSED = manifest.traffic("batch_closed")

# one request's body and the whole schedule's, as the parent sends them
PARENT = {
    "batch_closed": dict(
        requests=6144, at=0,
        sha256="ef3343706c3b3dfdfb58ab4b61b9198eb8333c00e25d53881ae36ceaa054"
               "a3b3",
        body='{"tokens": [[12980, 24005, 20417, 12736, 1525, 23135, 16336, '
             '32476, 11409, 22908, 2334, 16122, 24423, 26735, 27116, 22655, '
             '24044, 29627, 12128, 25767, 17891, 1800, 14086, 30779, 23697, '
             '12211, 28789, 11569, 32645, 11547, 2640, 24635, 31814, 21149, '
             '2450, 18191, 23738, 9221, 4680, 20947, 13236, 14656, 8582, '
             '9615, 9990, 19196, 8610, 28492, 29782, 27089, 28788, 10089, '
             '21305, 20813, 23373, 175, 29860, 24791, 1135, 23162, 8234, '
             '12690, 29458, 18899, 1088, 12284, 5935, 11641, 9043, 27203, '
             '29393, 11687, 11884, 10788, 26853, 17360, 30492, 23039, 19547, '
             '3340, 7909, 22015, 29001, 25330, 26272, 26390, 27797, 28495, '
             '8221, 4873, 2352, 14951, 19336, 18600, 30119, 3061, 5749, '
             '26925, 31792, 3755, 3934, 5567, 12186, 5423, 11522, 1130, '
             '24898, 17777, 8060, 3489, 31828, 6317, 26148, 24827, 19059, '
             '19574, 23244, 3574, 1230, 22927, 32206, 23828, 23473, 23138, '
             '26919, 31726, 26394, 22951]], "max_new_tokens": 8}'),
    "chat_open": dict(
        requests=584, at=11,
        sha256="1ae1aa2b0e966045177c9471efeb1922294e3d0aa1feffd8cfaaab7078f6"
               "26ef",
        body='{"tokens": [[30058, 19329, 7342, 24308, 21343, 19994, 21284, '
             '16392, 8826, 29808, 8737, 15791, 20085, 1275, 1738, 17289, '
             '30173, 22326, 13573, 17729, 8809, 7691, 17737, 4516, 11428, '
             '20207, 32249, 14510, 29304, 13481, 5899, 22364]], '
             '"max_new_tokens": 16}'),
}


def _requests(mix, seed=SEED, vocab=32768):
    if mix["kind"] == "closed_loop":
        return [r for plan in loadgen.closed_loop_schedule(mix, seed, vocab)
                for r in plan]
    return loadgen.open_loop_schedule(mix, 30.0, seed, vocab)


def _sizes(reqs):
    return [(r.due_s, r.prompt, r.n_out) for r in reqs]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_a_mix_without_the_key_sends_the_bytes_it_sent(name):
    mix, want = manifest.traffic(name), PARENT[name]
    assert "request_fields" not in mix
    reqs = _requests(mix)
    assert len(reqs) == want["requests"]
    assert all(r.fields == {} for r in reqs)
    bodies = [loadgen.body_of(r) for r in reqs]
    assert bodies[want["at"]] == want["body"]
    # every request of the schedule: lengths, prompts and bytes
    assert hashlib.sha256("\n".join(bodies).encode()).hexdigest() == \
        want["sha256"]


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_the_fields_are_the_mixes_own_draw_and_repeat(mix):
    with_fields = {**mix, "request_fields": FIELDS}
    a, b = _requests(with_fields), _requests(with_fields)
    other_seed = _requests(with_fields, seed=7)
    assert [r.fields for r in a] == [r.fields for r in b]
    assert [r.fields for r in a] == [r.fields for r in other_seed]
    assert [r.prompt for r in a] != [r.prompt for r in other_seed]
    assert {r.fields["steps"] for r in a} == set(STEPS["choices"])
    assert {r.fields["threshold"] for r in a} == {0.9, 0.95}
    assert {r.fields["tag"] for r in a} == {"b32"}
    # the key moves nothing else: gaps, lengths and prompts as without it
    assert _sizes(a) == _sizes(_requests(mix))
    # another `mix_seed` draws other fields; a field added beside it or
    # the file's order of them moves no field that was there
    moved = _requests({**with_fields, "mix_seed": mix["mix_seed"] + 1})
    assert [r.fields["steps"] for r in a[:200]] != \
        [r.fields["steps"] for r in moved[:200]]
    alone = _requests({**mix, "request_fields": {"steps": STEPS}})
    assert [r.fields["steps"] for r in a] == \
        [r.fields["steps"] for r in alone]
    # the values go out as the file writes them
    assert json.loads(loadgen.body_of(a[0])) == {
        "tokens": [a[0].prompt], "max_new_tokens": a[0].n_out, **a[0].fields}
    assert type(a[0].fields["steps"]) is int


def test_weights_are_kept_over_ten_thousand_draws():
    mix = {"mix_seed": 2407, "request_fields": {"steps": STEPS}}
    drawn = Counter(f["steps"] for f in loadgen.draw_fields(mix, 10_000))
    for value, weight in zip(STEPS["choices"], STEPS["weights"]):
        assert drawn[value] / 10_000 == pytest.approx(weight / 8, abs=0.015)
    even = {"mix_seed": 2407,
            "request_fields": {"steps": {"choices": [1, 2, 4, 8]}}}
    drawn = Counter(f["steps"] for f in loadgen.draw_fields(even, 10_000))
    assert all(abs(n / 10_000 - 0.25) < 0.015 for n in drawn.values())
    assert loadgen.draw_fields({"mix_seed": 1}, 3) == [{}, {}, {}]


@pytest.mark.parametrize("mix", [
    {**OPEN, "output_len": {"choices": [16, 32, 64, 128]}},
    {**CLOSED, "first_output_step": 0,
     "prompt_len": {"choices": [64, 128, 256, 512]},
     "output_len": {"choices": [16, 32, 64, 128]}}], ids=["open", "closed"])
def test_a_reorder_moves_a_requests_fields_with_its_lengths(mix):
    mix = {**mix, "request_fields": FIELDS}
    key = lambda r: (len(r.prompt), r.n_out,                  # noqa: E731
                     json.dumps(r.fields, sort_keys=True))
    plain = [key(r) for r in _requests(mix)]
    a = [key(r) for r in _requests({**mix, "seed_reorders": True}, seed=1)]
    b = [key(r) for r in _requests({**mix, "seed_reorders": True}, seed=2)]
    assert a != plain and a != b
    # the same requests in another order: lengths and fields as one
    assert Counter(a) == Counter(plain) == Counter(b)
    assert len(Counter(plain)) > 40


@pytest.mark.parametrize("bad,why", [
    ({"tokens": {"fixed": 1}}, "may not name 'tokens'"),
    ({"max_new_tokens": {"choices": [1, 2]}}, "may not name 'max_new_tokens'"),
    ({"steps": {"dist": "lognormal", "median": 4, "sigma": 1}},
     "unknown field spec"),
    ({"steps": {"choices": []}}, "unknown field spec"),
    ({"steps": 4}, "unknown field spec"),
], ids=["tokens", "max_new_tokens", "lognormal", "no-choice", "bare-value"])
def test_a_reserved_name_or_an_unknown_spec_is_refused(bad, why, tmp_path,
                                                       monkeypatch):
    mix = {**CLOSED, "request_fields": bad}
    with pytest.raises(ValueError, match=why):
        loadgen.closed_loop_schedule(mix, 1, 1000)
    with pytest.raises(ValueError, match=why):
        loadgen.open_loop_schedule({**OPEN, "request_fields": bad}, 5.0, 1,
                                   1000)
    # and when the file is read
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "bad.json").write_text(json.dumps(mix))
    monkeypatch.setattr(manifest, "BENCH", str(tmp_path))
    with pytest.raises(ValueError, match=why):
        manifest.traffic("bad")


def test_a_rehearsal_overrides_a_field_by_its_name():
    mix = {**CLOSED, "request_fields": FIELDS}
    mix["rehearsal"] = {**mix["rehearsal"],
                        "request_fields": {"steps": {"fixed": 2}}}
    _, small = cell.apply_rehearsal({}, mix)
    assert small["request_fields"] == {**FIELDS, "steps": {"fixed": 2}}
    reqs = _requests(small, vocab=100)
    assert {r.fields["steps"] for r in reqs} == {2}
    assert {r.fields["threshold"] for r in reqs} == {0.9, 0.95}


TODAYS_KEYS = ["cut_at_end", "attempted", "failed", "completed_in_window",
               "tokens_per_s", "tokens_ended_in_window_per_s", "latency_ms",
               "late_ms", "plane_overhead_ms", "per_replica",
               "unanswered_at_window_end"]


@pytest.mark.parametrize("closed", [False, True], ids=["open", "closed"])
def test_by_field_adds_up_to_the_totals(closed):
    R = loadgen.Record
    recs = [R(0, 0.0, sent_s=0.0, done_s=4.0, ok=True, got=40, want=40,
              fields={"steps": 2, "tag": "x"}),
            R(1, 0.0, sent_s=1.0, done_s=9.0, ok=True, got=40, want=40,
              fields={"steps": 4, "tag": "x"}),
            R(2, 0.0, sent_s=2.0, done_s=12.0, ok=True, got=24, want=24,
              fields={"steps": 2, "tag": "x"}),
            # a short answer and a refused one are counted, credited nothing
            R(3, 0.0, sent_s=0.0, done_s=5.0, ok=False, got=7, want=40,
              fields={"steps": 4, "tag": "x"}),
            R(4, 0.0, sent_s=0.0, done_s=5.0, ok=False, status=500,
              fields={"steps": 8, "tag": "x"})]
    s = loadgen.summarize(recs, 10.0, 1e6, closed=closed)
    # a closed loop's summary also lists the requests it credits (PR 59)
    credited = ["credited"] if closed else []
    assert list(s) == TODAYS_KEYS + ["by_field"] + credited
    assert s["by_field"] == {
        "steps": {"2": {"completed": 2, "tokens": 64},
                  "4": {"completed": 1, "tokens": 40},
                  "8": {"completed": 0, "tokens": 0}},
        "tag": {"x": {"completed": 3, "tokens": 104}}}
    for per_value in s["by_field"].values():
        assert sum(v["completed"] for v in per_value.values()) == \
            s["attempted"] - s["failed"]
        assert sum(v["tokens"] for v in per_value.values()) == \
            sum(r.got for r in recs if r.ok)
    # no field, no key: the summary's keys are today's
    for r in recs:
        r.fields = {}
    assert list(loadgen.summarize(recs, 10.0, 1e6, closed=closed)) == \
        TODAYS_KEYS + credited


@pytest.fixture
def echo():
    """A server that answers as the deployment does and keeps the bodies
    it was sent."""
    from aiohttp import web

    seen, started, box = [], threading.Event(), {}

    async def handle(request):
        raw = await request.read()
        seen.append(raw)
        body = json.loads(raw)
        return web.json_response({
            "tokens": [[1] * body["max_new_tokens"]], "engine_s": 0.0,
            "replica": "r0"})

    def serve():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        app = web.Application()
        app.router.add_post("/x", handle)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        box["port"] = site._server.sockets[0].getsockname()[1]
        box["loop"] = loop
        started.set()
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    assert started.wait(10)
    yield f"http://127.0.0.1:{box['port']}/x", seen
    box["loop"].call_soon_threadsafe(box["loop"].stop)
    thread.join(10)


def test_both_loops_send_the_fields_and_keep_them_in_the_records(echo):
    url, seen = echo
    mix = {"kind": "closed_loop", "clients": 3, "mix_seed": 5,
           "requests_per_client": 2, "prompt_len": {"fixed": 4},
           "output_len": {"fixed": 3}, "request_fields": FIELDS}
    plans = loadgen.closed_loop_schedule(mix, SEED, 100)
    recs = loadgen.run_closed_loop(url, plans, 5.0, 5.0)
    sent = {r.idx: r for p in plans for r in p}
    assert len(recs) == 6 and all(r.ok for r in recs)
    assert all(r.fields == sent[r.idx].fields and r.fields for r in recs)
    assert sorted(seen) == sorted(loadgen.body_of(r).encode()
                                  for r in sent.values())
    assert all(set(json.loads(raw)) == {"tokens", "max_new_tokens", "steps",
                                        "threshold", "tag"} for raw in seen)
    s = loadgen.summarize(recs, 5.0, 1e6, closed=True)
    assert s["by_field"]["tag"] == {"b32": {"completed": 6, "tokens": 18}}
    # the open loop, and a request with no field beside one with
    del seen[:]
    reqs = [loadgen.Request(0, 0.0, [1, 2], 2, fields={"steps": 4}),
            loadgen.Request(1, 0.05, [3, 4], 2)]
    recs = loadgen.run_open_loop(url, reqs, 0.2, 5.0)
    assert [r.fields for r in recs] == [{"steps": 4}, {}]
    assert sorted(seen) == sorted([
        b'{"tokens": [[1, 2]], "max_new_tokens": 2, "steps": 4}',
        b'{"tokens": [[3, 4]], "max_new_tokens": 2}'])
