"""The selecting-latent-attention configuration (`dots3-note-prev-l5-ep8`)
and its cell: the configuration's file against the catalog, the seeded
weights, the system against the plain reference at the rehearsal's
widths, the controls, the rooflines' counts by hand, every new reader on
a recorded context and on another cell's, the manifest's entries, and
the cell's rehearsal."""

import inspect
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import test_bench_manifest as held
from benchmarks import manifest, roofline_sparse_latent as rl
from benchmarks import weights_dots3 as wts
from benchmarks.planes import serve_sparse_latent as plane
from benchmarks.reference import dots3 as ref

NAME, CELL = "dots3-note-prev-l5-ep8", "dots3_docqa_closed_16k"
CFG = manifest.config(NAME)
TINY = {**CFG["model"], **CFG["rehearsal"]["model"]}
TDEP = {**CFG["deployment"], **CFG["rehearsal"]["deployment"]}
STD = 0.2
NEW_METRICS = ("dsa_device_share", "dsa_index_roofline",
               "dsa_sparse_decode_roofline", "swa_decode_roofline",
               "ep_moe_routed_roofline", "prefix_hit_token_share",
               "dsa_selected_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _reference_logits(toks, seed, dtype=jnp.float32):
    ends = wts.ends(TINY, seed, dtype, std=STD)
    x = ref.embed(jnp.asarray(toks), ends["tok_emb"])
    for l in range(TINY["num_hidden_layers"]):
        x, _ = ref.layer(x, wts.layer(TINY, TDEP, seed, l, dtype, std=STD),
                         qblock=8, hgroup=2,
                         **ref.layer_kwargs(TINY, l, TDEP["expert_offset"]))
    return np.asarray(ref.head(x, ends["final_norm"], ends["lm_head"],
                               TINY["rms_norm_eps"]))


def _system_logits(toks, seed, dtype, control=None, params=None):
    from ray_tpu.models import dots3

    mcfg = plane.model_config(TINY, TDEP, dtype, control)
    if params is None:
        params = wts.params(TINY, TDEP, seed, dtype, std=STD)
    return np.asarray(dots3.forward(mcfg, params, jnp.asarray(toks)))


def _toks(seed=4, T=48):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], size=T)


# ----------------------------------------------------------------------
def test_the_reference_imports_nothing_from_the_program():
    import benchmarks.reference.deepseek_v3 as base

    for mod in (ref, base):
        assert "ray_tpu" not in inspect.getsource(mod).replace(
            "nothing from `ray_tpu`", "")


def test_the_configuration_copies_the_catalog_and_lists_its_cuts():
    m = CFG["model"]
    assert all(CFG[k] == v for k, v in m.items())       # the two copies
    cut = {"num_hidden_layers": 5, "n_routed_experts": 32,
           "vocab_size": 19008,
           "layer_types": ["full_attention", "full_attention"]
           + ["sliding_attention"] * 3}
    assert {k: m[k] for k in cut} == cut
    assert sorted(CFG["reduced"]) == sorted(cut)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(l) for l in f
                       if l.startswith('{"name": "dots3-note-prev"'))
        assert CFG["source"] == row["source_url"]
        assert {k: v for k, v in m.items() if k not in cut} == \
            {k: v for k, v in row["config"].items() if k not in cut}
        assert row["config"]["layer_types"][:5] == m["layer_types"]
        pub = CFG["published"]
        assert all(row["config"][k] == pub[k] for k in (
            "num_hidden_layers", "n_routed_experts", "vocab_size"))
    for k in ("apply_mla_qkv_lora_rescale", "attention_gate_type", "indexer",
              "sliding_window_size", "rope"):
        assert k in CFG["assumed"], k
    dep = CFG["deployment"]
    assert (dep["chips_per_layer"], dep["router_experts"],
            dep["experts_per_chip"], dep["vocab_shards"]) == (8, 256, 32, 8)
    assert dep["experts_per_chip"] * dep["chips_per_layer"] == \
        CFG["published"]["n_routed_experts"]
    assert m["vocab_size"] * dep["vocab_shards"] == \
        CFG["published"]["vocab_size"]
    assert "towers" in CFG["model_why"] and "multi-token" in CFG["model_why"]


def test_the_cut_weighs_what_the_file_says():
    """8.17 GB of bfloat16 weights, counted from the shapes."""
    m, dep = CFG["model"], CFG["deployment"]
    count = lambda shp: sum(int(np.prod(s)) for s in shp.values())  # noqa: E731
    layers = [count(wts.shapes(m, dep, wts.kind_of(m, l)))
              for l in range(m["num_hidden_layers"])]
    ends = 2 * m["vocab_size"] * m["hidden_size"] + m["hidden_size"]
    assert [round(n / 1e6, 1) for n in layers] == [356.4, 923.9, 870.7,
                                                   870.7, 870.7]
    total = sum(layers) + ends
    assert 4.08e9 < total < 4.095e9 and 8.16e9 < 2 * total < 8.19e9
    e = CFG["engine"]
    per_token = 2 * (2 * (576 + 128) + 3 * 1088)
    assert per_token == 9344
    pool = (e["kv_blocks"] + 1) * e["block_size"] * 2 * (
        2 * (640 + 128) + 3 * 1152)
    assert 2.9e9 < pool < 3.0e9
    # the fullest device over 25% of 16 GB before any activation
    assert 2 * total + pool > 0.6 * 16e9
    mix = manifest.traffic("docqa_closed_16k_a128")
    longest = max(mix["prompt_len"]["choices"]) + mix["output_len"]["fixed"]
    assert e["max_len"] > longest and e["prefill_chunk"] % e["block_size"] == 0
    # whole lanes of positions: the indexer's scores are [rows, max_len]
    assert e["max_len"] % 128 == 0
    assert e["kv_blocks"] >= 8 * 1024 + e["slots"] * -(
        -(longest - 16384) // e["block_size"]) + 600 * 7


def test_layer_from_seed_equals_the_tree():
    params = wts.params(TINY, TDEP, 9, jnp.float32, std=STD)
    for l in (0, 1, 4):
        again = wts.layer(TINY, TDEP, 9, l, jnp.float32, std=STD)
        assert set(again) == set(params["layers"][l])
        for k, v in again.items():
            assert np.array_equal(np.asarray(v),
                                  np.asarray(params["layers"][l][k])), (l, k)
    assert params["layers"][1]["router"].shape[-1] == TDEP["router_experts"]
    assert params["layers"][1]["e_gate"].shape[0] == TINY["n_routed_experts"]
    assert "idx_wq" in params["layers"][1] and \
        "idx_wq" not in params["layers"][2]


def test_the_system_equals_the_reference_float32():
    toks = _toks()
    assert len(toks) > TINY["index_topk"] and len(toks) > \
        TINY["sliding_window_size"]
    want = _reference_logits(toks, 4)
    got = _system_logits(toks, 4, jnp.float32)
    assert np.abs(got - want).max() < 2e-4
    assert want.std() > 0.3


@pytest.mark.parametrize("control", ["fp8", "dense_full", "window_off"])
def test_a_control_is_another_result(control):
    """Each control moves the logits of a context where its mechanism
    binds by far more than float32 against float32 differs."""
    toks = _toks()
    want = _reference_logits(toks, 4)
    params = wts.params(TINY, TDEP, 4, jnp.float32, std=STD)
    if control == "fp8":
        params = plane.fp8_weights(params)
        assert np.array_equal(np.asarray(params["layers"][1]["router"]),
                              np.asarray(wts.layer(TINY, TDEP, 4, 1,
                                                   jnp.float32,
                                                   std=STD)["router"]))
    got = _system_logits(toks, 4, jnp.float32,
                         None if control == "fp8" else control, params)
    assert np.abs(got - want)[-8:].max() > 1e-2


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="controls are"):
        plane.BenchSparseLatentService(
            {**CFG, "model": TINY, "deployment": TDEP}, {}, 1,
            {"bench_dir": "/nonexistent", "rehearse": True,
             "control": "int8"})


def test_the_documents_are_the_clients_own():
    from benchmarks import loadgen

    mix = manifest.traffic("docqa_closed_16k_a128")
    mix = {**mix, **mix["rehearsal"]}
    docs = plane.documents(mix, 11, 256)
    assert len(docs) == 2 and {len(d) for d in docs} == {64}
    for reqs in loadgen.closed_loop_schedule(mix, 11, 256):
        for r in reqs:
            assert r.prompt[:64] in docs and len(r.prompt) - 64 in (8, 16)


def test_the_traffic_is_the_issues():
    mix = manifest.traffic("docqa_closed_16k_a128")
    assert (mix["kind"], mix["clients"], mix["mix_seed"]) == \
        ("closed_loop", 192, 2407)
    assert mix["shared_prefix"] == {"groups": 8, "len": 16384,
                                    "min_suffix": 16}
    assert mix["prompt_len"] == {"choices": [16448, 16512, 16640],
                                 "weights": [2, 2, 1]}
    assert mix["first_output_step"] == 8 and mix["requests_per_client"] == 48
    assert mix["output_len"]["fixed"] in (128, 256, 64)  # or a named fallback


# -- the rooflines' counts, by hand at one shape ------------------------
def test_roofline_counts_at_the_cells_shapes():
    peaks = manifest.peaks("TPU v5 lite")
    rows, T = 120, 16600
    live = rows * T
    w = rl.dsa_index(live, rows, 64, 128)
    assert w["bytes"] == live * 256 + rows * 64 * 129 * 2 + live * 4
    assert w["flops"] == 2 * 64 * 128 * live
    # 1.99M live tokens x 260 B = 518 MB: 0.63 ms; 32.6 GFLOP: 0.17 ms
    assert rl.least_seconds(w, peaks)["bound"] == "memory"
    assert 0.60e-3 < rl.least_seconds(w, peaks)["seconds"] < 0.67e-3
    s = rl.dsa_sparse_decode(rows * 2048, rows, 128, 576, 512)
    assert s["bytes"] == rows * 2048 * 1152 + rows * 128 * 1088 * 2
    assert s["flops"] == 2 * 128 * 1088 * rows * 2048
    # 283 MB + 33 MB: 0.39 ms; 68 GFLOP: 0.35 ms
    assert rl.least_seconds(s, peaks)["bound"] == "memory"
    assert 0.36e-3 < rl.least_seconds(s, peaks)["seconds"] < 0.41e-3
    v = rl.swa_decode(rows * 513, rows, 64, 1088, 1024)
    assert v["bytes"] == rows * 513 * 2176 + rows * 64 * 2112 * 2
    assert 0.18e-3 < rl.least_seconds(v, peaks)["seconds"] < 0.22e-3
    e = rl.ep_moe_routed(rows, 8, 32, 256, 4 * 31.5, 4, 5120, 1536)
    expert = 3 * 5120 * 1536 * 2
    assert expert == 47_185_920
    assert 0.995 < (126 * expert) / e["bytes"] <= 1.0
    assert e["flops"] == 2 * 3 * 5120 * 1536 * rows * 4
    assert 7.1e-3 < rl.least_seconds(e, peaks)["seconds"] < 7.4e-3


# -- the readers ---------------------------------------------------------
def _ctx(scopes=None, ticks=()):
    engine = {"request_ring": [], "cache_bytes_per_token": 9344}
    return {"plane": "serve", "config": CFG, "setup_s": 10.5, "seconds": 4.0,
            "peaks": manifest.peaks("TPU v5 lite"),
            "client": {"per_replica": {"1": 0}},
            "replicas": [{"rid": "1", "tick_ring": list(ticks),
                          "engine": engine,
                          "trace": {"devices": 1, "scopes": scopes or {}}}]}


def test_every_new_reader_reads_a_recorded_context(monkeypatch):
    monkeypatch.setenv("RT_BENCH_T0", "1000.0")
    tick = {"t_wall": 1011.2, "prefix_hit_tokens": 10 * 16384,
            "prefill_tokens": 1280,
            "active": 120, "live_tokens": 120 * 16600, "row_steps": 1024,
            "row_steps_live": 960, "experts_touched": 126.0,
            "experts_total": 128, "expert_load_max": 11, "experts_held": 32,
            "dsa_selected_share": 2048 / 16600,
            "window_rows_live": 120 * 513}
    # 10 programs of 8 steps: 80 steps; 2 full and 3 window layers
    scopes = {"programs_s": 2.4, "program_calls": 10, "dsa_index": 0.48,
              "dsa_select": 0.16, "dsa_attn": 0.32, "swa_attn": 0.12,
              "moe_routed": 0.72, "moe_router": 0.02, "moe_shared": 0.05}
    # set-up's ticks (before 1010.5) and the drain's are not the window's
    warm = {**tick, "t_wall": 1003.0, "dsa_selected_share": 1.0,
            "prefix_hit_tokens": 0, "prefill_tokens": 99999}
    late = {**tick, "t_wall": 1014.6, "dsa_selected_share": 1.0}
    ctx = _ctx(scopes, [warm, tick, {**tick, "t_wall": 1012.9,
                                     "prefill_tokens": 1920},
                        {"t_wall": 1013.0, "active": 0, "live_tokens": 0},
                        late])
    read = lambda n: manifest.layer_metric(n).read(ctx)  # noqa: E731
    assert read("dsa_device_share") == pytest.approx(40.0)
    assert read("dsa_selected_share") == pytest.approx(2048 / 16600)
    assert read("prefix_hit_token_share") == pytest.approx(
        100 * 327680 / (327680 + 3200))
    # 0.48 s / 160 layer-steps = 3 ms against 0.63 ms
    assert 20 < read("dsa_index_roofline") < 22
    # 0.32 s / 160 = 2 ms against 0.39 ms
    assert 18 < read("dsa_sparse_decode_roofline") < 20.5
    # 0.12 s / 240 = 0.5 ms against 0.20 ms
    assert 37 < read("swa_decode_roofline") < 43
    # 0.72 s / 80 = 9 ms against 7.26 ms
    assert 79 < read("ep_moe_routed_roofline") < 82
    for name in NEW_METRICS:
        v = read(name)
        assert v is not None and (v <= 100 or "share" in name), name


def test_the_new_readers_read_nothing_on_another_cell_or_the_parent():
    """A cell of another model, and this cell on a program without the
    scopes, counters or the account's column: None, never an error."""
    kanana = {**_ctx({"programs_s": 1.2, "program_calls": 10,
                      "moe_routed": 0.8},
                     [{"active": 64, "live_tokens": 64 * 1400,
                       "experts_touched": 730.0, "experts_total": 768,
                       "expert_load_max": 9, "row_steps_live": 500}]),
              "config": manifest.config("kanana-2-30b-a3b-l7")}
    parent = _ctx({}, [{"active": 120, "live_tokens": 1000,
                        "prefill_tokens": 1280}])
    os.environ["RT_BENCH_T0"] = "1000.0"
    try:
        for name in NEW_METRICS:
            for ctx in (kanana, parent, {"plane": "train"}):
                assert manifest.layer_metric(name).read(ctx) is None, name
    finally:
        del os.environ["RT_BENCH_T0"]


def test_decode_step_ms_reads_the_programs_the_plane_names():
    ctx = _ctx()
    ctx["replicas"][0]["trace"]["kernels"] = {"paged_decode": {
        "seconds": 2.4, "calls": 10, "op_seconds": 0.44, "op_calls": 400}}
    assert manifest.layer_metric("decode_step_ms").read(ctx) == \
        pytest.approx(1e3 * 2.4 / 80)


def test_the_verdict_holds_the_hits_and_the_documents():
    check = {"sampled": 8, "tokens": 1024, "mean_margin": 0.01,
             "max_margin": 0.5, "documents": 4}
    good = {"replicas": [{"check": check,
                          "hits": {"not_a_whole_hit": 0}}]}
    assert plane.verdict(good, CFG)["correct"]
    missed = {"replicas": [{"check": check,
                            "hits": {"not_a_whole_hit": 2}}]}
    few = {"replicas": [{"check": {**check, "documents": 3},
                         "hits": {"not_a_whole_hit": 0}}]}
    none = {"replicas": [{"check": check}]}
    for ctx in (missed, few, none):
        assert not plane.verdict(ctx, CFG)["correct"]


def _served(n_docs=8, full_in=None, doc=32, span=256, shorts=24, fulls=18):
    """A window's answers as the replica keeps them: behind each of
    `n_docs` documents `shorts` callers' first answers of 8..248 tokens
    and `fulls` of full length (none behind a document outside
    `full_in`), and the warm-up's short prompt, which is no document's."""
    rng = np.random.default_rng(5)
    out = [([1] * 16, [2, 2])]
    for d in range(n_docs):
        head = [100 + d] * doc
        for j in range(shorts):
            out.append((head + [7] * 8, [3] * (8 * (1 + j % 31))))
        if full_in is None or d in full_in:
            for _ in range(fulls):
                out.append((head + [7] * 8, [4] * span))
    return [out[i] for i in rng.permutation(len(out))]


@pytest.mark.parametrize("seed", [0, 7, 4900000101, 2**31 + 11])
def test_the_sample_is_full_answers_over_every_document(seed):
    """8 picks of 256 tokens whatever the seed: `sampled_tokens_at_least`
    then fails only where the program served too little."""
    served = [s for s in _served() if len(s[0]) > 32]
    pick = plane.sample_answers(served, 32, 8, 256, seed)
    assert len(pick) == len(set(pick)) == 8
    assert [len(served[i][1]) for i in pick] == [256] * 8
    assert len({served[i][0][0] for i in pick}) == 8   # every document
    assert pick == plane.sample_answers(served, 32, 8, 256, seed)
    other = plane.sample_answers(served, 32, 8, 256, seed + 1)
    assert sorted(other) != sorted(pick)               # the seed draws


def test_the_sample_falls_back_only_where_a_document_has_no_full_answer():
    served = [s for s in _served(full_in={0, 1, 2}) if len(s[0]) > 32]
    pick = plane.sample_answers(served, 32, 8, 256, 3)
    by_doc = {}
    for i in pick:
        by_doc.setdefault(served[i][0][0] - 100, []).append(len(served[i][1]))
    assert sorted(by_doc) == list(range(8))            # still round-robin
    assert all(by_doc[d] == [256] for d in (0, 1, 2))
    assert all(n < 256 for d in range(3, 8) for n in by_doc[d])
    # fewer answers than asked for: all of them, once
    few = served[:5]
    assert sorted(plane.sample_answers(few, 32, 8, 256, 3)) == list(range(5))


# -- the manifest ---------------------------------------------------------
def check_the_manifest_finds_every_new_file():
    """What PR 41 added is held BY NAME, and the cell's readers as a set
    that must be there, not as a count: a later PR may list one more
    (`test_bench_manifest.py::test_a_list_can_grow` runs this against a
    manifest that grew)."""
    man = manifest.manifest()
    cell = manifest.cell(CELL)
    assert cell in man["workloads"] and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (NAME, "docqa_closed_16k_a128")
    entry = next(c for c in man["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"]
    assert os.path.exists(os.path.join(manifest.REPO, entry["file"]))
    assert os.path.exists(os.path.join(manifest.REPO,
                                       CFG["reference"]["file"]))
    assert CFG["plane"] == "serve_sparse_latent"
    e2e = [e["name"] for e in manifest.metrics_for(CELL, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    per_layer = manifest.metrics_for(CELL, "per_layer")
    names = [p["name"] for p in per_layer]
    assert [n for n in names if n in NEW_METRICS] == list(NEW_METRICS)
    # at least the sixteen shared readers of a closed cell, and none that
    # counts another model's widths
    assert held.CLOSED_SHARED <= set(names)
    assert not {"mla_decode_roofline", "moe_routed_roofline",
                "paged_decode_roofline"} & set(names)
    for p in per_layer:
        mod = manifest.layer_metric(p["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            p["layer"], p["unit"], p["source"], p["moves"]), p["name"]
        assert p["moves"] == "serve_tokens_per_s"
    listed = [p for p in man["per_layer"] if p["name"] in NEW_METRICS]
    assert [p["name"] for p in listed] == list(NEW_METRICS)
    for p in listed:
        assert p["workloads"] == [CELL]


def test_the_manifest_finds_every_new_file():
    check_the_manifest_finds_every_new_file()


def test_the_parent_fails_at_once_on_the_missing_model(monkeypatch):
    monkeypatch.setattr(plane, "NEED", (("models", "no_such_model.py"),))
    with pytest.raises(RuntimeError, match="cannot run"):
        plane.run({"name": CELL}, CFG, {}, None, 0.0)


def test_the_cells_rehearsal_leaves_nothing_running():
    import test_bench_guard as guard

    proc, mark = guard.start(CELL)
    out, err = proc.communicate(timeout=900)
    assert proc.returncode == 3, err[-3000:]
    assert "rehearsal passed" in err
    assert '"correct"' not in out.strip().splitlines()[-1]
    assert '"metrics"' not in out
    # the rows of `correct` are the run's last lines on standard error
    assert '"window_requests_not_a_whole_document_hit", "value": 0' in err
    guard.assert_clean(mark)
