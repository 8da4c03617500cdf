"""The engine-account readers (PR 37) on hand-made contexts: the window
they cut out of `stats()["tick_account"]` (by `RT_BENCH_T0`, or by the
lifecycle records without it), the values they compute, the pooling
over replicas, the `None` they give a program that keeps no account
(the parent of that PR), and their entries in `BENCHMARK.json`."""

import pytest

from benchmarks import manifest
from benchmarks.layer_metrics import _engine_account as ea

FIELDS = ["sec", "ticks", "tick_us", "wait_us", "plan_us", "prefill_us",
          "dispatch_us", "device_wait_us", "harvest_host_us", "cpu_us",
          "proc_cpu_us", "host_gap_us", "gap_harvest_host_us",
          "gap_plan_us", "starved", "stalled", "row_steps",
          "row_steps_live", "prefill_calls", "prefill_rows",
          "prefill_tokens", "prefill_padded_tokens"]
CLOSED = ["mistral7b_batch_closed", "kanana2_batch_closed_1k",
          "brumby14b_batch_closed_1k", "lfm2_batch_closed_512"]
CHAT = ["mistral7b_chat_open", "mistral7b_chat_open_r4",
        "mistral7b_chat_open_long"]
# name -> (unit, better): twelve readers, these six and each again as
# `<name>.chat`, each listed in `BENCHMARK.json` as `entry_for` says
READERS = {
    "engine_tick_host_busy_ms": ("ms", "lower"),
    "engine_device_wait_share": ("%", "higher"),
    "engine_starved_gap_share": ("%", "lower"),
    "engine_live_row_share": ("%", "higher"),
    "engine_prefill_padding_share": ("%", "lower"),
    "engine_stall_ticks": ("count", "lower"),
}
BOTH = [n + tail for n in READERS for tail in ("", ".chat")]


def row(sec, **cols):
    return [sec] + [cols.get(k, 0) for k in FIELDS[1:]]


def busy(sec, ticks=8):
    """A second of a closed cell: 8 ticks of 125 ms, 100 of them the
    wait for the device, 18 the host's own work."""
    return row(sec, ticks=ticks, tick_us=125_000 * ticks,
               plan_us=2_000 * ticks, prefill_us=4_000 * ticks,
               dispatch_us=1_000 * ticks, device_wait_us=100_000 * ticks,
               harvest_host_us=11_000 * ticks, row_steps=512 * ticks,
               row_steps_live=448 * ticks, prefill_calls=2, prefill_rows=6,
               prefill_tokens=3_000, prefill_padded_tokens=4_096)


def ctx_with(accounts, setup_s=100.0, seconds=10.0, rings=None,
             answers=None):
    """`accounts`: {rid: rows}."""
    rings = rings or {}
    return {
        "plane": "serve", "setup_s": setup_s, "seconds": seconds,
        "client": {"per_replica": answers or {}},
        "replicas": [{"rid": rid,
                      "engine": {"tick_account": {"fields": FIELDS,
                                                  "rows": rows},
                                 "request_ring": rings.get(rid, [])}}
                     for rid, rows in accounts.items()],
    }


def read(name, ctx):
    return manifest.layer_metric(name).read(ctx)


@pytest.fixture
def t0(monkeypatch):
    """The run began at wall second 1000.25: with `setup_s` 100 the
    window is [1100.25, 1110.25), its whole seconds 1101..1109."""
    monkeypatch.setenv("RT_BENCH_T0", "1000.25")


# warm-up's compile ticks before the window, the drain's after it
WARM = [row(s, ticks=1, tick_us=4_000_000, prefill_us=3_900_000, stalled=1,
            row_steps=512) for s in range(1090, 1101)]
DRAIN = [row(s, ticks=20, tick_us=100_000, wait_us=900_000)
         for s in range(1110, 1114)]
ROWS = WARM + [busy(s) for s in range(1101, 1110)] + DRAIN
CTX = ctx_with({"7": ROWS})


def test_the_window_is_where_setup_ends_for_the_cells_seconds(t0):
    assert ea.window(CTX) == (1100.25, 1110.25)
    sums = ea.window_sums(CTX)
    # nine whole seconds: 1100 straddles the start, 1110 the end
    assert sums["ticks"] == 9 * 8 and sums["stalled"] == 0
    assert sums["tick_us"] == 9 * 8 * 125_000 and sums["wait_us"] == 0


@pytest.mark.parametrize("name,want", [
    ("engine_tick_host_busy_ms", 18.0),
    ("engine_device_wait_share", 80.0),
    ("engine_starved_gap_share", 0.0),
    ("engine_live_row_share", 87.5),
    ("engine_prefill_padding_share", 100.0 * (1 - 3_000 / 4_096)),
    ("engine_stall_ticks", 0.0),
])
def test_each_reader_on_a_busy_window(t0, name, want):
    assert read(name, CTX) == pytest.approx(want)
    assert read(name + ".chat", CTX) == pytest.approx(want)


def test_a_starved_second_a_stall_and_the_blocked_time_count(t0):
    rows = [busy(s) for s in range(1101, 1110)]
    # a second whose ticks found their chunks done: 8 gaps of 25 ms, and
    # a second in which the loop sat blocked for half of it
    rows[2] = row(1103, ticks=8, tick_us=500_000, wait_us=500_000,
                  device_wait_us=2_000, starved=8, host_gap_us=200_000,
                  gap_harvest_host_us=120_000, gap_plan_us=30_000,
                  row_steps=4_096, row_steps_live=1_024)
    rows[5] = row(1106, ticks=1, tick_us=3_500_000, device_wait_us=3_400_000,
                  stalled=1, row_steps=512, row_steps_live=448)
    ctx = ctx_with({"7": rows})
    lived = 7 * 1_000_000 + 1_000_000 + 3_500_000
    assert read("engine_starved_gap_share", ctx) == pytest.approx(
        100.0 * 200_000 / lived)
    assert read("engine_device_wait_share", ctx) == pytest.approx(
        100.0 * (7 * 800_000 + 2_000 + 3_400_000) / lived)
    assert read("engine_stall_ticks", ctx) == 1.0
    assert read("engine_live_row_share", ctx) == pytest.approx(
        100.0 * (57 * 448 + 1_024) / (57 * 512 + 4_096))


def test_the_replicas_of_r4_are_pooled(t0):
    quiet = [row(s, ticks=2, tick_us=100_000, wait_us=900_000,
                 device_wait_us=60_000, harvest_host_us=8_000,
                 row_steps=1_024, row_steps_live=128)
             for s in range(1101, 1110)]
    ctx = ctx_with({"1": ROWS, "2": quiet, "3": quiet, "4": []})
    sums = ea.window_sums(ctx)
    assert sums["ticks"] == 9 * (8 + 2 + 2)
    # every tick counts once, whichever replica ran it
    assert read("engine_tick_host_busy_ms.chat", ctx) == pytest.approx(
        (8 * 18.0 + 4 * 4.0) / 12)
    assert read("engine_device_wait_share.chat", ctx) == pytest.approx(
        100.0 * (800_000 + 2 * 60_000) / 3_000_000)


def rec(t_submit, first_token_s=0.5, decode_s=1.5):
    return {"seq": 0, "t_done": t_submit + first_token_s + decode_s,
            "status": "ok", "queue_s": 0.01, "prefill_dispatch_s": 0.01,
            "first_token_s": first_token_s, "decode_s": decode_s,
            "harvests": 4, "tokens_in": 128, "tokens_hit": 0,
            "tokens_out": 32}


def test_without_the_stamp_the_window_starts_at_the_earliest_submit(
        monkeypatch):
    monkeypatch.delenv("RT_BENCH_T0", raising=False)
    # three warm-up records, then the 20 the client was answered
    ring = [rec(1080.0 + i) for i in range(3)] + [
        rec(1100.4 + 0.4 * i) for i in range(20)]
    ctx = ctx_with({"7": ROWS}, rings={"7": ring}, answers={"7": 20})
    start, end = ea.window(ctx)
    assert (start, end) == pytest.approx((1100.4, 1110.4))
    assert ea.window_sums(ctx)["ticks"] == 9 * 8
    assert read("engine_device_wait_share", ctx) == pytest.approx(80.0)
    # no stamp and no record: no window, so nothing to read
    bare = ctx_with({"7": ROWS})
    assert ea.window(bare) is None
    assert read("engine_device_wait_share", bare) is None


@pytest.mark.parametrize("name", BOTH)
def test_a_program_without_the_account_reads_none(t0, name):
    parent = {"plane": "serve", "setup_s": 100.0, "seconds": 10.0,
              "client": {"per_replica": {"7": 20}},
              "replicas": [{"rid": "7", "engine": {"active": 0}}]}
    assert read(name, parent) is None
    assert read(name, {"plane": "train", "setup_s": 1.0, "seconds": 1.0}) \
        is None
    # an account with no tick in the window: warm-up and drain alone
    assert read(name, ctx_with({"7": WARM + DRAIN})) is None


@pytest.mark.parametrize("name", ["engine_live_row_share",
                                  "engine_prefill_padding_share"])
def test_a_share_of_nothing_is_none(t0, name):
    idle = [row(s, ticks=3, tick_us=3_000, wait_us=900_000)
            for s in range(1101, 1110)]
    assert read(name, ctx_with({"7": idle})) is None
    assert read("engine_device_wait_share", ctx_with({"7": idle})) == 0.0


def entry_for(name):
    """The reader's `per_layer` entry in `BENCHMARK.json`: the closed
    cells' moves their tokens per second, its `.chat` twin in the open
    cells the request tail.  A PR that adds a cell appends the cell's
    name to the list it belongs to, here and there."""
    unit, better = READERS[name.removesuffix(".chat")]
    chat = name.endswith(".chat")
    return {"name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": "engine",
            "moves": "request_p95_ms" if chat else "serve_tokens_per_s",
            "workloads": CHAT if chat else CLOSED}


def check_the_manifest_finds_every_new_file():
    """The twelve entries held by name, each list by the cells that
    stood in it first (`test_bench_manifest.py::test_a_list_can_grow`
    runs this against a manifest that grew)."""
    for name in BOTH:
        check_the_manifest_lists(name)


@pytest.mark.parametrize("name", BOTH)
def test_the_manifest_lists_each_reader_as_its_module_says(name):
    check_the_manifest_lists(name)


def check_the_manifest_lists(name):
    want = entry_for(name)
    mod = manifest.layer_metric(name)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        want["layer"], want["unit"], want["source"], want["moves"])
    man = manifest.manifest()
    reports = {c: {e["name"] for e in manifest.metrics_for(c, "end_to_end")}
               for c in want["workloads"]}
    assert all(want["moves"] in got for got in reports.values()), reports
    entry = next(p for p in man["per_layer"] if p["name"] == name)
    # a later cell's name may follow these in `workloads`
    assert {**entry, "workloads": entry["workloads"][:len(want["workloads"])]} \
        == want
    for cell in entry["workloads"]:
        assert name in [p["name"] for p in
                        manifest.metrics_for(cell, "per_layer")]


def test_the_readers_cells_exist_and_the_old_number_stays():
    man = manifest.manifest()
    names = [p["name"] for p in man["per_layer"]]
    # the old number stays beside `engine_tick_host_busy_ms` until a
    # benchmark PR retires it
    assert "engine_tick_host_ms" in names and "engine_ttft_p90_ms" in names
    assert len(names) == len(set(names))
    assert set(CLOSED + CHAT) <= {c["name"] for c in man["workloads"]}


def test_the_readers_fields_are_the_engines():
    from ray_tpu.serve import llm_engine

    assert tuple(FIELDS) == llm_engine.ACCOUNT_FIELDS
