"""The manifest against its contract, the files it names, and the
traffic generator's promises."""

import asyncio
import json
import math
import os
import re
import shutil
import sys
import threading

import numpy as np
import pytest

from benchmarks import loadgen, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
M = manifest.manifest()
CELLS = [c["name"] for c in M["workloads"]]
# the seventeen per-layer readers every closed cell reports (sixteen that
# list the closed cells, and `serve_mfu`, which lists none and moves what
# they all report): a module that holds a closed cell's entries holds at
# least these, as a set
CLOSED_SHARED = {
    "serve_mfu",
    "serve_plane_overhead_p50_ms", "engine_compiles_in_window",
    "engine_batch_occupancy", "engine_tick_host_ms",
    "request_p95_ms.saturated", "device_idle_share.serve", "decode_step_ms",
    "prefill_device_share", "engine_inter_token_p50_ms",
    "engine_prefill_rows_per_program", "engine_tick_host_busy_ms",
    "engine_device_wait_share", "engine_starved_gap_share",
    "engine_live_row_share", "engine_prefill_padding_share",
    "engine_stall_ticks"}


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(manifest.REPO, "BENCHMARK.json")) < 65536
    for word in M["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
    assert any(w.startswith(tuple(M["paths"])) for w in M["command"])


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_keys(kind):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[kind]
    names = [e["name"] for e in M[kind]]
    assert len(names) == len(set(names))
    for e in M[kind]:
        assert set(e) <= allowed and NAME.match(e["name"]), e
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for w in e.get("workloads", []):
            assert w in CELLS


def test_end_to_end_bounds_and_setup():
    e2e = {e["name"]: e for e in M["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for e in M["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")


def check_every_cell_reports_enough_and_uses_a_known_config(M):
    cells = [c["name"] for c in M["workloads"]]
    configs = {c["name"]: c for c in M["configs"]}
    assert len({(c["config"], c["traffic"]) for c in M["workloads"]}) == len(cells)
    for c in M["workloads"]:
        assert c["config"] in configs and c["chips"] in (1, 4)
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        e2e = [e["name"] for e in manifest.metrics_for(c["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_for(c["name"], "per_layer")
    assert {c["config"] for c in M["workloads"]} == set(configs)
    four = sum(c["chips"] == 4 for c in M["workloads"])
    assert four <= max(1, len(cells) // 4)


def test_every_cell_reports_enough_and_uses_a_known_config():
    check_every_cell_reports_enough_and_uses_a_known_config(M)


def _copy_of_bench(tmp_path, *subs):
    bench = tmp_path / "benchmarks"
    for sub in subs:
        shutil.copytree(os.path.join(manifest.BENCH, sub), bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return bench


def manifest_checks():
    """Every test module of this directory that holds manifest entries
    gives its check the callable form
    `check_the_manifest_finds_every_new_file()`; they are found by
    walking the directory, so a PR that adds a module adds no name
    here."""
    import importlib

    here = os.path.dirname(os.path.abspath(__file__))
    found = {}
    for file in sorted(os.listdir(here)):
        if file.startswith("test_bench_") and file.endswith(".py"):
            mod = importlib.import_module(file[:-3])
            check = getattr(mod, "check_the_manifest_finds_every_new_file",
                            None)
            if check is not None:
                found[file[:-3]] = check
    return found


def grow(tmp_path, monkeypatch):
    """A copy of the manifest and of the files it names, grown as the
    next PR that brings a closed cell grows them, and `manifest` turned
    to the copy.  Nothing that is there is edited: one configuration,
    one cell with a traffic file and a `closed_sizes` file of its own and
    one per-layer entry of its own at the END of their lists, one more
    per-layer entry of a cell that is there (PR 52's case), and the
    cell's name appended to EVERY list that all the closed cells share."""
    bench = _copy_of_bench(tmp_path, "traffic", "configs", "layer_metrics",
                           "reference", "needed_flops")
    sizes = tmp_path / "closed_sizes"
    shutil.copytree(CLOSED_DIR, sizes)
    grown = manifest.manifest()
    closed = {c["name"] for c in grown["workloads"]
              if manifest.traffic(c["traffic"])["kind"] == "closed_loop"}
    last = grown["configs"][-1]
    cfg = manifest.load_json(os.path.join(manifest.REPO, last["file"]))
    (bench / "configs" / "one-more.json").write_text(
        json.dumps({**cfg, "name": "one-more"}))
    grown["configs"].append({**last, "name": "one-more",
                             "file": "benchmarks/configs/one-more.json"})
    mix = {**manifest.traffic("batch_closed"),
           "clients": 2 * cfg["engine"]["slots"],
           "request_fields": {"steps": {"choices": [2, 4]}}}
    (bench / "traffic" / "one_more_closed.json").write_text(json.dumps(mix))
    (sizes / "one_more_closed.json").write_text(json.dumps({
        "tick": 0.2, "slots": cfg["engine"]["slots"],
        "callers": mix["clients"], "answer": mix["output_len"]["fixed"],
        "first_step": mix["first_output_step"], "cell": "one_more_cell",
        "measured_at": "nowhere: test_a_list_can_grow"}))
    grown["workloads"].append({
        "name": "one_more_cell", "config": "one-more",
        "traffic": "one_more_closed", "chips": 1, "why": "a list can grow"})
    def lists_them(e):
        return closed <= set(e.get("workloads", ()))

    # an entry that lists no cell is every cell's that reports what it
    # moves (`serve_mfu`): shared too, and nobody appends to it
    moved = {e["name"] for e in grown["end_to_end"] if lists_them(e)}
    shared = [e for e in grown["end_to_end"] + grown["per_layer"]
              if lists_them(e)
              or ("workloads" not in e and e.get("moves") in moved)]
    for e in shared:
        if "workloads" in e:
            e["workloads"].append("one_more_cell")
    reader = ('LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_counter", '
              '"serve_tokens_per_s"\n\ndef read(ctx):\n    return None\n')
    earlier = grown["workloads"][-2]["name"]
    for name, cell in (("one_more_metric", "one_more_cell"),
                       ("one_more_of_an_earlier_cell", earlier)):
        (bench / "layer_metrics" / (name + ".py")).write_text(reader)
        grown["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_counter", "layer": "engine",
            "moves": "serve_tokens_per_s", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(grown))
    monkeypatch.setattr(manifest, "REPO", str(tmp_path))
    monkeypatch.setattr(manifest, "BENCH", str(bench))
    monkeypatch.setattr(sys.modules[__name__], "CLOSED_DIR", str(sizes))
    return grown, [e["name"] for e in shared], earlier


def test_a_list_can_grow(tmp_path, monkeypatch):
    """What the next PR that brings a cell does to the manifest (`grow`).
    No test may hold an entry by its place: EVERY module's check of what
    an earlier PR added passes on the grown copy.  (The check of
    `test_bench_window_full.py` as PR 51 wrote it, `workloads[-1]` and
    `per_layer[-6:]`, fails there: CHANGES.md, PR 54, has the run.)"""
    before = manifest.manifest()
    checks = manifest_checks()   # imported while `manifest` is the tree's
    grown, shared, earlier = grow(tmp_path, monkeypatch)
    assert manifest.manifest()["workloads"][-1]["name"] == "one_more_cell"
    # the per-layer entries every closed cell reports and its end-to-end one
    assert {"serve_tokens_per_s", *CLOSED_SHARED} <= set(shared)
    mine = [p["name"] for p in manifest.metrics_for("one_more_cell",
                                                    "per_layer")]
    assert mine == shared[1:] + ["one_more_metric"]
    was = [p["name"] for p in before["per_layer"]
           if earlier in p.get("workloads", ())
           or ("workloads" not in p and p["name"] in shared)]
    assert [p["name"] for p in manifest.metrics_for(earlier, "per_layer")] \
        == was + ["one_more_of_an_earlier_cell"]
    assert {"test_bench_hybrid", "test_bench_sparse_latent",
            "test_bench_window_full"} <= set(checks)
    for module, check in checks.items():
        try:
            check()
        except AssertionError as e:
            raise AssertionError(f"{module} holds an entry by its place: "
                                 f"{e}") from e
    check_every_cell_reports_enough_and_uses_a_known_config(grown)
    check_the_mixes_in_the_table_are_the_closed_mixes()
    assert "one_more_closed" in closed_sizes()
    # the grown cell's requests carry the field its traffic file names
    plan = loadgen.closed_loop_schedule(manifest.traffic("one_more_closed"),
                                        3, 1000)
    assert {r.fields["steps"] for p in plan for r in p} == {2, 4}


def test_each_layer_metric_moves_a_metric_its_cells_report():
    e2e = {e["name"] for e in M["end_to_end"]}
    for p in M["per_layer"]:
        assert p["moves"] in e2e
        for cell in p.get("workloads", CELLS):
            mine = {e["name"] for e in
                    manifest.metrics_for(cell, "end_to_end")}
            if "workloads" in p:
                assert p["moves"] in mine, (p["name"], cell)


def test_every_named_file_exists_and_declares_what_the_manifest_says():
    for c in M["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in M["paths"]))
        cfg = manifest.load_json(os.path.join(manifest.REPO, c["file"]))
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert cfg["source"] == c["source"]
        assert os.path.exists(os.path.join(manifest.REPO,
                                           cfg["reference"]["file"]))
    for c in M["workloads"]:
        assert manifest.traffic(c["traffic"])["kind"] in (
            "open_loop", "closed_loop", "train_stream")
    for p in M["per_layer"]:
        mod = manifest.layer_metric(p["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            p["layer"], p["unit"], p["source"], p["moves"]), p["name"]
        assert callable(mod.read)


def test_no_width_is_reduced():
    width = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$"
                       r"|head_dim|expand|experts_per_tok|n_embd)")
    for c in M["configs"]:
        assert not [k for k in c["reduced"] if width.search(k)]


def test_a_dropped_in_file_is_found_with_no_code_change(tmp_path, monkeypatch):
    bench = _copy_of_bench(tmp_path, "traffic", "configs", "layer_metrics")
    shutil.copy(os.path.join(manifest.BENCH, "peaks.json"), bench)
    (bench / "traffic" / "bursty_new.json").write_text(json.dumps({
        "kind": "open_loop", "rate_per_s": 3.0, "arrival_cv": 3.0,
        "mix_seed": 1, "prompt_len": {"fixed": 64},
        "output_len": {"fixed": 32}}))
    (bench / "configs" / "new-model.json").write_text(json.dumps(
        {"name": "new-model", "plane": "serve"}))
    (bench / "layer_metrics" / "new_metric.py").write_text(
        'LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "host_clock", '
        '"setup_s"\n\ndef read(ctx):\n    return ctx["x"] * 2.0\n')
    monkeypatch.setattr(manifest, "BENCH", str(bench))
    mix = manifest.traffic("bursty_new")
    reqs = loadgen.open_loop_schedule(mix, 20.0, 5, 1000)
    assert 30 <= len(reqs) <= 90 and {len(r.prompt) for r in reqs} == {64}
    assert manifest.config("new-model")["plane"] == "serve"
    assert manifest.layer_metric("new_metric").read({"x": 2.0}) == 4.0


def test_peaks_reject_an_unknown_device_kind():
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in benchmarks/peaks.json"):
        manifest.peaks("TPU v9 imaginary")


# ------------------------------------------------------------- traffic
MIX = manifest.traffic("chat_open")


def test_schedule_is_deterministic_in_the_seed():
    a = loadgen.open_loop_schedule(MIX, 30.0, 2**31 + 7, 32768)
    b = loadgen.open_loop_schedule(MIX, 30.0, 2**31 + 7, 32768)
    c = loadgen.open_loop_schedule(MIX, 30.0, 8, 32768)
    assert [(r.due_s, r.prompt, r.n_out) for r in a] == \
        [(r.due_s, r.prompt, r.n_out) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_every_seed_offers_the_same_work():
    """In the cells' mixes the seed changes token ids only; with
    `seed_reorders` it also shuffles: same sizes and gaps, other order."""
    a = loadgen.open_loop_schedule(MIX, 30.0, 1, 32768)
    b = loadgen.open_loop_schedule(MIX, 30.0, 2, 32768)
    assert [(r.due_s, len(r.prompt), r.n_out) for r in a] == \
        [(r.due_s, len(r.prompt), r.n_out) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    shuffled = {**MIX, "seed_reorders": True}
    a = loadgen.open_loop_schedule(shuffled, 30.0, 1, 32768)
    b = loadgen.open_loop_schedule(shuffled, 30.0, 2, 32768)
    size = lambda rs: sorted((len(r.prompt), r.n_out) for r in rs)  # noqa: E731
    assert size(a) == size(b) and len(a) == len(b)
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.due_s for r in rs]), 9))  # noqa: E731
    assert gaps(a) == gaps(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(0 <= r.due_s <= 30.0 for r in a)


def test_only_grid_lengths_are_drawn():
    grid = set(loadgen.grid_values(MIX["prompt_len"]["grid"]))
    reqs = loadgen.open_loop_schedule({**MIX, "rate_per_s": 50.0}, 30.0, 3,
                                      32768)
    assert {len(r.prompt) for r in reqs} <= grid
    assert len({len(r.prompt) for r in reqs}) > 8
    assert {r.n_out for r in reqs} <= set(MIX["output_len"]["choices"])
    assert loadgen.possible_lengths(MIX["prompt_len"]) == sorted(grid)
    with pytest.raises(ValueError):
        loadgen.possible_lengths({"dist": "lognormal", "median": 9, "sigma": 1})


def test_closed_loop_first_answers_are_staggered():
    mix = manifest.traffic("batch_closed")
    plans = loadgen.closed_loop_schedule(mix, 4, 32768)
    assert len(plans) == mix["clients"]
    firsts = [p[0].n_out for p in plans]
    assert len(set(firsts)) == mix["output_len"]["fixed"] // mix["first_output_step"]
    assert all(r.n_out == mix["output_len"]["fixed"] for p in plans for r in p[1:])
    assert all(len(r.prompt) == 128 for p in plans for r in p)


def test_latency_counts_from_the_due_instant_and_misses_count():
    recs = [loadgen.Record(0, due_s=1.0, sent_s=1.5, done_s=2.0, ok=True,
                           got=10, want=10, engine_s=0.4),
            loadgen.Record(1, due_s=2.0, sent_s=2.0, done_s=11.0, ok=True,
                           got=20, want=20, engine_s=8.9),
            loadgen.Record(2, due_s=3.0, sent_s=3.0, ok=False)]
    s = loadgen.summarize(recs, seconds=10.0, miss_ms=99_000.0)
    assert s["latency_ms"] == [1000.0, 9000.0, 99_000.0]   # from DUE, not sent
    assert s["late_ms"] == [500.0, 0.0, 0.0]
    assert s["attempted"] == 3 and s["failed"] == 1
    assert s["tokens_per_s"] == 1.0      # only the answer inside the window
    assert s["unanswered_at_window_end"] == 2
    assert loadgen.percentile(s["latency_ms"], 95) == 99_000.0
    assert loadgen.percentile([], 95) is None


# ---------------------------------------- a closed loop's rate (PR 49)
# the closed mixes' sizes, a file a mix: `closed_sizes/<traffic>.json`,
# each with its cell and its cell's tick in the window (PERF.md section
# 2): what one harvest is of a 30 s count.  A PR that adds a closed mix
# adds its file; the set check and the three checks below take it as a
# case, and nothing here is edited
CLOSED_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "closed_sizes")
SIZE_KEYS = ("tick", "slots", "callers", "answer", "first_step")
CHUNK = 8    # tokens a live row gets a tick, in every closed cell


def closed_sizes() -> dict:
    """traffic file's name -> its row, `cell` and `measured_at` with it."""
    rows = {}
    for file in sorted(os.listdir(CLOSED_DIR)):
        if file.endswith(".json"):
            rows[file[:-5]] = manifest.load_json(os.path.join(CLOSED_DIR,
                                                              file))
    return rows


def _size(mix: str) -> dict:
    """The row as `_ticking_engine` takes it."""
    row = manifest.load_json(os.path.join(CLOSED_DIR, mix + ".json"))
    return {k: row[k] for k in SIZE_KEYS}


def _ticking_engine(tick, slots, callers, answer, first_step, chunk=CHUNK,
                    admit=16, seconds=30.0, shift=0.0):
    """A closed loop's records against an engine that ticks: at every
    tick the rows that have their tokens are handed back TOGETHER (a
    harvest; their callers send again at once), up to `admit` waiting
    requests take free slots, every live row gets `chunk` tokens.  The
    completions come in a wave of harvests, as the cells' do."""
    recs, queue, live = [], [], []

    def send(c, t, first):
        want = (first_step * (1 + c % (answer // first_step)) if first
                else answer)
        r = loadgen.Record(len(recs), t, sent_s=t, want=want)
        r.client = c
        recs.append(r)
        queue.append(r)

    for c in range(callers):
        send(c, 0.0, True)
    t = shift
    while queue or live:
        for row in [x for x in live if x[1] == 0]:
            live.remove(row)
            r = row[0]
            r.done_s, r.got, r.ok, r.status = t, r.want, True, 200
            if t < seconds:
                send(r.client, t, False)
        for _ in range(min(admit, slots - len(live), len(queue))):
            r = queue.pop(0)
            live.append([r, -(-r.want // chunk)])
        for row in live:
            row[1] -= 1
        t += tick
    return recs


def _both(recs, seconds):
    s = loadgen.summarize(recs, seconds, 1e9, closed=True)
    return s["tokens_per_s"], s["tokens_ended_in_window_per_s"]


def _apart(values):
    return (max(values) - min(values)) / (sum(values) / len(values))


def check_the_mixes_in_the_table_are_the_closed_mixes():
    man = manifest.manifest()
    closed = {c["traffic"]: c for c in man["workloads"]
              if manifest.traffic(c["traffic"])["kind"] == "closed_loop"}
    rows = closed_sizes()
    assert set(closed) == set(rows)
    for name, size in rows.items():
        assert set(size) == {*SIZE_KEYS, "cell", "measured_at"}, name
        mix = manifest.traffic(name)
        assert mix["clients"] == size["callers"]
        assert mix["output_len"] == {"fixed": size["answer"]}
        assert mix["first_output_step"] == size["first_step"]
        # the row's slots are its cell's engine's
        assert closed[name]["name"] == size["cell"]
        cfg = manifest.config(closed[name]["config"])
        assert cfg["engine"]["slots"] == size["slots"], name
        assert cfg["engine"]["chunk"] == CHUNK, name


def test_the_mixes_in_the_table_are_the_closed_mixes():
    check_the_mixes_in_the_table_are_the_closed_mixes()


@pytest.mark.parametrize("mix", sorted(closed_sizes()))
def test_closed_rate_does_not_step_with_the_windows_end(mix):
    """ONE run's records read at window ends swept across one tick: the
    answers that ended inside the window step by a harvest, the tokens
    as they are produced do not."""
    size = _size(mix)
    recs = _ticking_engine(seconds=31.0, **size)
    reads = [_both(recs, 30.0 - size["tick"] * j / 10) for j in range(11)]
    assert _apart([new for new, _ in reads]) < 0.002
    harvest = size["tick"] / 30.0
    assert _apart([old for _, old in reads]) > 0.8 * harvest
    if harvest > 0.01:
        assert _apart([old for _, old in reads]) > 0.01


@pytest.mark.parametrize("mix", sorted(closed_sizes()))
def test_closed_rate_does_not_step_with_a_shift_of_the_run(mix):
    """The whole run later by 0-1 tick (the engine's phase against the
    client's clock): the same."""
    size = _size(mix)
    reads = [_both(_ticking_engine(shift=size["tick"] * j / 10, **size), 30.0)
             for j in range(10)]
    new, old = _apart([n for n, _ in reads]), _apart([o for _, o in reads])
    # the callers' first requests, whose lives hold the shift, still run
    # at the instant the reading starts (`CLOSED_READ_FROM` of the
    # window, 6 s of 30) where a tick is long or a request's life in the
    # engine (`answer / CHUNK` ticks) passes that instant: 0.24-0.35%
    # there (the old reading 0.46-1.4%); under 0.03% where both are short.
    # `tick > 0.3` alone stood in for this until a row came with a tick
    # of 0.25 and a life of 16 s (`mixed_closed_8k_a512`: 0.236%)
    life = size["answer"] / CHUNK * size["tick"]
    still_run = size["tick"] > 0.3 or life > loadgen.CLOSED_READ_FROM * 30.0
    assert new < (0.004 if still_run else 0.0005) and new < old


@pytest.mark.parametrize("mix", sorted(closed_sizes()))
def test_closed_rate_reads_a_two_percent_faster_engine_as_two_percent(mix):
    size = _size(mix)
    base, _ = _both(_ticking_engine(**size), 30.0)
    fast, _ = _both(_ticking_engine(
        **{**size, "tick": size["tick"] / 1.02}), 30.0)
    # +1.80 to +2.17: the start's climb ends 2% sooner too, and a part of
    # it lies after S/5 (the old reading: +0.46 to +2.75)
    assert 100.0 * (fast / base - 1.0) == pytest.approx(2.0, abs=0.25)


def test_closed_rate_credits_answers_only_and_counts_the_cut():
    R = loadgen.Record
    recs = [R(0, 0.0, sent_s=0.0, done_s=20.0, ok=True, got=100, want=100),
            R(1, 0.0, sent_s=5.0, done_s=15.0, ok=True, got=50, want=50),
            # a short answer, a refused one, one that never came back
            R(2, 0.0, sent_s=0.0, done_s=10.0, ok=False, got=7, want=50),
            R(3, 0.0, sent_s=0.0, done_s=10.0, ok=False, status=500),
            R(4, 0.0, sent_s=8.0, cut=True)]
    s = loadgen.summarize(recs, seconds=10.0, miss_ms=1e6, closed=True)
    # over [2, 10]: 100 x 8/20 of request 0, 50 x 5/10 of request 1
    assert s["tokens_per_s"] == pytest.approx((40.0 + 25.0) / 8.0)
    assert loadgen.produced_per_s(recs, 10.0) == s["tokens_per_s"]
    # by the window's end: 100 x 10/20 of request 0, 50 x 5/10 of request 1
    assert [loadgen._produced_by(r, 10.0) for r in recs[:2]] == [50.0, 25.0]
    assert s["cut_at_end"] == 1 and s["attempted"] == 4 and s["failed"] == 2
    assert s["completed_in_window"] == 0


def test_the_old_reading_stays_under_a_name_of_its_own():
    R = loadgen.Record
    recs = [R(0, 0.0, sent_s=0.0, done_s=4.0, ok=True, got=40, want=40),
            R(1, 0.0, sent_s=4.0, done_s=12.0, ok=True, got=40, want=40)]
    closed = loadgen.summarize(recs, 10.0, 1e6, closed=True)
    opened = loadgen.summarize(recs, 10.0, 1e6)
    assert closed["tokens_ended_in_window_per_s"] == 4.0
    assert opened["tokens_ended_in_window_per_s"] == 4.0
    assert opened["tokens_per_s"] == 4.0        # an open loop's, as it was
    # [2, 10]: 40 x 2/4 of the first, 40 x 6/8 of the second
    assert closed["tokens_per_s"] == pytest.approx((20.0 + 30.0) / 8.0)


def test_a_closed_cells_verdict_holds_the_cut_to_nought():
    from benchmarks.planes import serve

    cfg = {"reference": {"mean_margin_limit": 0.1, "max_margin_limit": 1.0,
                         "min_tokens": 8}}
    check = {"sampled": 2, "tokens": 16, "mean_margin": 0.01,
             "max_margin": 0.2}
    ctx = {"replicas": [{"check": check}], "client": {"cut_at_end": 0},
           "traffic": {"kind": "closed_loop"}}
    v = serve.verdict(ctx, cfg)
    assert v["correct"] and ("cut_at_end", 0, 0) in v["rows"]
    ctx["client"]["cut_at_end"] = 1
    assert not serve.verdict(ctx, cfg)["correct"]
    # an open loop's late answers are late, not wrong: no such row
    ctx["traffic"] = {"kind": "open_loop"}
    v = serve.verdict(ctx, cfg)
    assert v["correct"] and len(v["rows"]) == 3


def test_the_ring_the_readers_average_ends_with_the_window():
    from benchmarks.planes import serve

    results = [{"tick_ring": [{"seq": 1, "t_wall": 99.0}, {"seq": 2},
                              {"seq": 3, "t_wall": 129.9},
                              {"seq": 4, "t_wall": 130.0},
                              {"seq": 5, "t_wall": 141.0}]},
               {"tick_ring": []}]
    serve.ring_to(results, 130.0)
    # set-up's ticks and one without a stamp stay, the drain's go
    assert [t["seq"] for t in results[0]["tick_ring"]] == [1, 2, 3]
    assert results[1]["tick_ring"] == []


def test_the_last_line_names_the_rows_that_failed():
    from benchmarks import cell

    rows = [("mean_margin_below_reference_argmax", 0.31, 0.1),
            ("sampled_tokens_at_least", -2048, -1024),
            ("max_margin_below_reference_argmax", math.inf, 5.0)]
    out = cell.checks_of({"rows": rows, "correct": False})
    assert out["failed_checks"] == [
        ["mean_margin_below_reference_argmax", 0.31, 0.1],
        ["max_margin_below_reference_argmax", None, 5.0]]
    assert [r[0] for r in out["checks"]] == [r[0] for r in rows]
    assert list(out) == ["failed_checks", "checks"]   # `checks` comes last
    json.loads(json.dumps(out, allow_nan=False))      # strict JSON
    assert cell.checks_of({"rows": rows[1:2]})["failed_checks"] == []


def test_open_loop_client_sends_on_schedule_against_a_slow_server():
    from aiohttp import web

    async def handle(request):
        body = await request.json()
        await asyncio.sleep(0.2)
        return web.json_response({
            "tokens": [[1] * body["max_new_tokens"]], "engine_s": 0.2,
            "replica": "r0"})

    started, box = threading.Event(), {}

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

    threading.Thread(target=serve, daemon=True).start()
    assert started.wait(10)
    reqs = [loadgen.Request(i, 0.05 * i, [1, 2, 3], 4) for i in range(10)]
    recs = loadgen.run_open_loop(f"http://127.0.0.1:{box['port']}/x", reqs,
                                 1.0, 5.0)
    box["loop"].call_soon_threadsafe(box["loop"].stop)
    assert all(r.ok for r in recs)
    # an open loop does not wait for answers: all ten were sent within
    # their schedule although each answer takes 0.2 s
    assert max(r.sent_s for r in recs) < 0.45 + 0.2
    lat = [(r.done_s - r.due_s) for r in recs]
    assert all(0.2 <= x < 0.6 for x in lat)
    assert math.isclose(recs[0].engine_s, 0.2)
