"""The readers of the engine's LAUNCH stamp (PR 57) on hand-made
contexts: the three columns it appends to `stats()["tick_account"]`,
cut to the window and pooled over replicas, the `None` they give a
program whose account lacks the columns (the parent of that PR), and
`stats()["launch_account"]`'s traced rows set against a trace's own count
of the same programs; and their five entries in `BENCHMARK.json`."""

import pytest

from benchmarks import manifest
from benchmarks.layer_metrics import _launch_account as la

# the account as the parent ships it, and the stamp's columns after it
PARENT = ["sec", "ticks", "tick_us", "wait_us", "plan_us", "prefill_us",
          "dispatch_us", "device_wait_us", "harvest_host_us", "cpu_us",
          "proc_cpu_us", "host_gap_us", "gap_harvest_host_us",
          "gap_plan_us", "starved", "stalled", "row_steps",
          "row_steps_live", "prefill_calls", "prefill_rows",
          "prefill_tokens", "prefill_padded_tokens"]
FIELDS = PARENT + ["launches", "launch_us", "launch_blocked_us"]
LAUNCH_FIELDS = ["program", "traced", "launches", "rows", "tokens",
                 "padded_tokens", "attended_pairs", "rows_live",
                 "live_tokens", "launch_us", "blocked"]
CLOSED = ["mistral7b_batch_closed", "kanana2_batch_closed_1k",
          "brumby14b_batch_closed_1k", "lfm2_batch_closed_512",
          "dots3_docqa_closed_16k", "mimo25_mixed_closed_8k",
          "sdar30b_blockgen_closed_512"]
CHAT = ["mistral7b_chat_open", "mistral7b_chat_open_r4",
        "mistral7b_chat_open_long"]
MIMO = "mimo25_mixed_closed_8k"
# name -> (unit, better, source, layer, moves, the cells that stood in
# its list first)
ENTRIES = {
    "engine_launch_blocked_share": (
        "%", "lower", "program_counter", "engine", "serve_tokens_per_s",
        CLOSED),
    "engine_launch_blocked_share.chat": (
        "%", "lower", "program_counter", "engine", "request_p95_ms", CHAT),
    "engine_tick_host_own_ms": (
        "ms", "lower", "program_counter", "engine", "serve_tokens_per_s",
        CLOSED),
    "engine_tick_host_own_ms.chat": (
        "ms", "lower", "program_counter", "engine", "request_p95_ms", CHAT),
    "gqa_full_prefill_roofline": (
        "%", "higher", "device_trace", "kernels", "serve_tokens_per_s",
        [MIMO]),
}
ACCOUNT_READERS = [n for n in ENTRIES if n.startswith("engine_")]


def read(name, ctx):
    return manifest.layer_metric(name).read(ctx)


def row(sec, fields=FIELDS, **cols):
    return [sec] + [cols.get(k, 0) for k in fields[1:]]


def many(sec, fields=FIELDS):
    """A second of a cell with many programs a tick: 2 ticks of 500 ms;
    of a tick's 180 ms in `prefill` + `dispatch`, 150 are launches that
    blocked (the device's queue was full), 300 the read of the chunk."""
    return row(sec, fields, ticks=2, tick_us=1_000_000, plan_us=8_000,
               prefill_us=340_000, dispatch_us=20_000,
               device_wait_us=600_000, harvest_host_us=30_000,
               launches=14, launch_us=312_000, launch_blocked_us=300_000)


def ctx_with(accounts, fields=FIELDS, setup_s=100.0, seconds=10.0):
    return {"plane": "serve", "setup_s": setup_s, "seconds": seconds,
            "client": {"per_replica": {}},
            "replicas": [{"rid": rid, "engine": {"tick_account": {
                "fields": fields, "rows": rows}}}
                for rid, rows in accounts.items()]}


@pytest.fixture
def t0(monkeypatch):
    """The run began at wall second 1000.25: with `setup_s` 100 the
    window is [1100.25, 1110.25), its whole seconds 1101..1109."""
    monkeypatch.setenv("RT_BENCH_T0", "1000.25")


# warm-up's ticks before the window (a launch that compiles is long and
# not blocked), the drain's after it
WARM = [row(s, ticks=1, tick_us=4_000_000, prefill_us=3_900_000,
            launches=1, launch_us=3_800_000) for s in range(1090, 1101)]
DRAIN = [row(s, ticks=20, tick_us=100_000, wait_us=900_000, launches=20,
             launch_us=9_000) for s in range(1110, 1114)]
ROWS = WARM + [many(s) for s in range(1101, 1110)] + DRAIN


def test_the_window_cuts_the_stamps_columns_as_it_cuts_the_others(t0):
    sums = la.launch_sums(ctx_with({"7": ROWS}))
    assert sums["ticks"] == 9 * 2 and sums["launches"] == 9 * 14
    assert sums["launch_us"] == 9 * 312_000
    assert sums["launch_blocked_us"] == 9 * 300_000


@pytest.mark.parametrize("name,want", [
    ("engine_launch_blocked_share", 30.0),
    # (8 + 340 + 20 + 30 - 300) ms a second of two ticks
    ("engine_tick_host_own_ms", 49.0),
])
def test_each_reader_on_a_window_of_many_programs_a_tick(t0, name, want):
    ctx = ctx_with({"7": ROWS})
    assert read(name, ctx) == pytest.approx(want)
    assert read(name + ".chat", ctx) == pytest.approx(want)
    # what the host needs is what was booked as its work, less the block
    busy = read("engine_tick_host_busy_ms", ctx)
    assert busy == pytest.approx(199.0) and want <= busy
    # the two waits for the device together: what the loop does not own
    assert read("engine_device_wait_share", ctx) + read(
        "engine_launch_blocked_share", ctx) == pytest.approx(90.0)


def test_two_replicas_are_pooled_over_the_windows_whole_seconds(t0):
    """A replica whose launches never block beside one whose do: every
    tick counts once, and the seconds that straddle the window's edges
    (1100: warm-up's, 1110: the drain's) are left out of both."""
    quick = [row(s, ticks=8, tick_us=1_000_000, plan_us=16_000,
                 prefill_us=24_000, dispatch_us=8_000,
                 device_wait_us=880_000, harvest_host_us=40_000,
                 launches=10, launch_us=6_000)
             for s in range(1100, 1111)]
    ctx = ctx_with({"1": ROWS, "2": quick})
    assert la.launch_sums(ctx)["ticks"] == 9 * (2 + 8)
    assert read("engine_launch_blocked_share", ctx) == pytest.approx(
        100.0 * 300_000 / 2_000_000)
    assert read("engine_tick_host_own_ms", ctx) == pytest.approx(
        (2 * 49.0 + 8 * 11.0) / 10)
    assert read("engine_tick_host_own_ms", ctx) == pytest.approx(
        read("engine_tick_host_busy_ms", ctx) - 300.0 / 10)


@pytest.mark.parametrize("name", ACCOUNT_READERS)
def test_a_parents_account_without_the_columns_reads_none(t0, name):
    rows = [many(s, PARENT) for s in range(1101, 1110)]
    parent = ctx_with({"7": rows}, fields=PARENT)
    assert all(len(r) == len(PARENT) for r in rows)
    assert read(name, parent) is None
    # ... though the account's older readers read it as ever
    assert read("engine_tick_host_busy_ms", parent) == pytest.approx(199.0)
    # no account at all, another plane, no tick in the window
    assert read(name, {"plane": "serve", "setup_s": 100.0, "seconds": 10.0,
                       "replicas": [{"rid": "7", "engine": {}}]}) is None
    assert read(name, {"plane": "train", "setup_s": 1.0,
                       "seconds": 1.0}) is None
    assert read(name, ctx_with({"7": WARM + DRAIN})) is None


# ----------------------------------------------------------------------
# the launch account against a trace
# ----------------------------------------------------------------------
KERNEL_2 = ("%prefill_attention.2 = bf16[4,16,2048,128]{3,2,1,0:T(8,128)"
            "(2,1)} custom-call(s32[1]{0:T(128)} %bitcast.741, ...)")
KERNEL_3 = ("%prefill_attention.3 = bf16[4,16,2048,128]{3,2,1,0:T(8,128)"
            "(2,1)S(1)} custom-call(s32[1]{0:T(128)} %bitcast.742, ...)")
CHUNK = "jit_prefill_chunk_n2048(5555016686970065520)"
PACKED = "jit_prefill_packed_n2048(6027660726162516413)"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def pairs(lo, n):
    return n * lo + n * (n + 1) // 2


# 38 chunks of 2,048 rows: 10 first chunks of a prompt (`lo` 0), 10 at
# 2,048, 9 at 4,096, 9 at 6,144; and 4 packed programs of two prompts
# of 1,024 each
CHUNK_LOS = [0] * 10 + [2048] * 10 + [4096] * 9 + [6144] * 9
CHUNK_PAIRS = sum(pairs(lo, 2048) for lo in CHUNK_LOS)
PACKED_PAIRS = 4 * 2 * pairs(0, 1024)


def launch_rows(chunk_launches=38, traced=1):
    return [
        ["prefill_chunk_n2048", traced, chunk_launches, chunk_launches,
         2048 * chunk_launches, 2048 * chunk_launches, CHUNK_PAIRS, 0, 0,
         41_000, 30],
        ["prefill_packed_n2048", traced, 4, 8, 8192, 8192, PACKED_PAIRS, 0,
         0, 4_000, 2],
        # the chunks that decode, and the session's untraced twin of a
        # prefill program: neither is the kernel's work
        ["decode_chunk_w545", traced, 8, 0, 0, 0, 0, 1016, 3_900_000,
         9_000, 1],
        ["prefill_chunk_n2048", 0, 900, 900, 2048 * 900, 2048 * 900,
         10 ** 12, 0, 0, 900_000, 700],
    ]


def traced_ctx(rows, module_calls, op_seconds, config=None):
    return {
        "plane": "serve", "peaks": PEAKS,
        "config": config or manifest.config("mimo-v2.5-l7-ep16"),
        "replicas": [{"rid": "0", "engine": {"launch_account": {
            "fields": LAUNCH_FIELDS, "rows": rows}},
            "trace": {"devices": 1, "window_s": 3.0,
                      "module_calls": module_calls,
                      "op_seconds": op_seconds}}]}


CALLS = {CHUNK: 38, PACKED: 4, "jit_decode_chunk_w545(126159)": 8}
SECONDS = {KERNEL_2: 0.1551, KERNEL_3: 0.1449,
           "%fusion.655 = bf16[2048,16384]{1,0} fusion(...)": 0.056}


def test_the_roofline_on_a_hand_made_trace():
    """2 x 64 heads x (192 + 128) operations a pair, the two full
    layers, at 197 TFLOP/s, over what the trace gives the kernel."""
    got = read("gqa_full_prefill_roofline",
               traced_ctx(launch_rows(), CALLS, SECONDS))
    flops = 2 * 64 * 320 * (CHUNK_PAIRS + PACKED_PAIRS) * 2
    assert got == pytest.approx(100.0 * flops / 197e12 / 0.3)
    assert 25.0 < got < 54.0
    # the same programs at `lo` 0 alone need less: the share falls
    first = launch_rows()
    first[0][6] = 38 * pairs(0, 2048)
    low = read("gqa_full_prefill_roofline",
               traced_ctx(first, CALLS, SECONDS))
    assert low == pytest.approx(
        100.0 * 2 * 64 * 320 * (38 * pairs(0, 2048) + PACKED_PAIRS) * 2
        / 197e12 / 0.3) and low < got


@pytest.mark.parametrize("launches,want", [
    # a tick launches in a burst: a session holds one tick's launches
    # more or fewer than the trace holds calls (36 against 44 on the
    # chip); a quarter of the 42 calls apart and more, it is not the
    # same span
    (38, True), (30, True), (46, True), (27, False), (49, False),
])
def test_the_roofline_refuses_launches_the_trace_did_not_see(launches,
                                                             want):
    got = read("gqa_full_prefill_roofline",
               traced_ctx(launch_rows(launches), CALLS, SECONDS))
    assert (got is not None) == want


def test_the_traced_launches_mean_is_scaled_to_the_traces_calls():
    """As on the chip: 30 chunk launches a session recorded, 37 calls of
    the program in the trace (a tick's burst apart): the 37 calls held
    37 times what a traced launch held on average, program by program."""
    rows = launch_rows(30)
    rows[0][6] = 30 * pairs(2048, 2048)          # every chunk at lo 2,048
    replica = traced_ctx(rows, {**CALLS, CHUNK: 37}, SECONDS)["replicas"][0]
    held = la.held_by_the_traced_calls(
        replica, ("prefill_chunk_n", "prefill_packed_n"), "attended_pairs")
    assert held == pytest.approx(37 * pairs(2048, 2048) + PACKED_PAIRS)
    assert la.held_by_the_traced_calls(
        replica, ("prefill_chunk_n",), "launches") == pytest.approx(37)
    # a program the trace never ran counts nothing
    replica["trace"]["module_calls"].pop(PACKED)
    assert la.held_by_the_traced_calls(
        replica, ("prefill_chunk_n", "prefill_packed_n"),
        "attended_pairs") == pytest.approx(37 * pairs(2048, 2048))


def test_the_roofline_reads_none_where_there_is_nothing_to_read():
    name = "gqa_full_prefill_roofline"
    ok = traced_ctx(launch_rows(), CALLS, SECONDS)
    assert read(name, ok) is not None
    # an untraced run; a parent with no launch account; an account whose
    # rows no session recorded; a trace without the kernel (another
    # route); another model's configuration; another plane
    untraced = traced_ctx(launch_rows(), CALLS, SECONDS)
    del untraced["replicas"][0]["trace"]
    assert read(name, untraced) is None
    parent = traced_ctx(launch_rows(), CALLS, SECONDS)
    del parent["replicas"][0]["engine"]["launch_account"]
    assert read(name, parent) is None
    assert read(name, traced_ctx(launch_rows(traced=0), CALLS,
                                 SECONDS)) is None
    assert read(name, traced_ctx(launch_rows(), CALLS, {
        k: v for k, v in SECONDS.items() if "fusion" in k})) is None
    assert read(name, traced_ctx(
        launch_rows(), CALLS, SECONDS,
        config=manifest.config("mistral-7b-v0.3-l16"))) is None
    assert read(name, {**ok, "plane": "train"}) is None
    assert read(name, {k: v for k, v in ok.items() if k != "peaks"}) is None


def test_the_traced_rows_are_found_by_program_prefix():
    replica = traced_ctx(launch_rows(), CALLS, SECONDS)["replicas"][0]
    held = la.traced_rows(replica, ("prefill_chunk_n", "prefill_packed_n"))
    assert sorted(held) == ["prefill_chunk_n2048", "prefill_packed_n2048"]
    assert sum(r["launches"] for r in held.values()) == 42
    assert sum(r["attended_pairs"] for r in held.values()) == (
        CHUNK_PAIRS + PACKED_PAIRS)
    (chunk,) = la.traced_rows(replica, ("decode_chunk_",)).values()
    assert (chunk["rows_live"], chunk["live_tokens"]) == (1016, 3_900_000)
    assert la.traced_rows(replica, ("suffix_prefill_",)) is None
    assert la.trace_calls(replica["trace"], "prefill_chunk_n2048") == 38
    assert la.trace_calls(replica["trace"], "decode_chunk_w545") == 8
    # by the whole name: `n2048` is not `n204`
    assert la.trace_calls(replica["trace"], "prefill_chunk_n204") == 0


def test_the_readers_fields_are_the_engines():
    from ray_tpu.serve import llm_engine

    assert tuple(PARENT) == llm_engine.ACCOUNT_FIELDS
    assert tuple(FIELDS) == (llm_engine.ACCOUNT_FIELDS
                             + llm_engine.ACCOUNT_LAUNCH_FIELDS)
    assert tuple(LAUNCH_FIELDS) == llm_engine.LAUNCH_ACCOUNT_FIELDS


# ----------------------------------------------------------------------
# the manifest
# ----------------------------------------------------------------------
def check_the_manifest_lists(name):
    unit, better, source, layer, moves, cells = ENTRIES[name]
    mod = manifest.layer_metric(name)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        layer, unit, source, moves)
    want = {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": cells}
    listed = [p for p in manifest.manifest()["per_layer"]
              if p["name"] == name]
    assert len(listed) == 1
    (entry,) = listed
    # a later cell's name may follow these in `workloads`
    assert {**entry, "workloads": entry["workloads"][:len(cells)]} == want
    for cell in entry["workloads"]:
        assert moves in {e["name"] for e in
                         manifest.metrics_for(cell, "end_to_end")}
        assert name in [p["name"] for p in
                        manifest.metrics_for(cell, "per_layer")]


def check_the_manifest_finds_every_new_file():
    """The five entries held by name, each list by the cells that stood
    in it first (`test_bench_manifest.py::test_a_list_can_grow` runs
    this against a manifest that grew): more may follow each."""
    for name in ENTRIES:
        check_the_manifest_lists(name)
    # the two closed lists name EVERY closed cell there is today, so the
    # next PR that brings a closed cell appends its name to them too
    closed = {c["name"] for c in manifest.manifest()["workloads"]
              if manifest.traffic(c["traffic"])["kind"] == "closed_loop"}
    for name in ("engine_launch_blocked_share", "engine_tick_host_own_ms"):
        entry = next(p for p in manifest.manifest()["per_layer"]
                     if p["name"] == name)
        assert closed <= set(entry["workloads"])


@pytest.mark.parametrize("name", list(ENTRIES))
def test_the_manifest_lists_each_reader_as_its_module_says(name):
    check_the_manifest_lists(name)


def test_the_manifest_finds_every_new_file():
    check_the_manifest_finds_every_new_file()
    # the numbers they stand beside stay until a `benchmark` issue
    # retires or re-points them
    names = [p["name"] for p in manifest.manifest()["per_layer"]]
    assert {"engine_tick_host_busy_ms", "engine_device_wait_share",
            "gqa_full_decode_roofline"} <= set(names)
    assert len(names) == len(set(names))
