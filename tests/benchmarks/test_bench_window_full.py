"""The window-and-full-attention configuration (`mimo-v2.5-l7-ep16`) and
its cell: the configuration's file against the catalog, the seeded
weights, the system against the plain reference at the rehearsal's
widths, the controls, the rooflines' counts by hand, every new reader on
a recorded context and on another cell's, the manifest's entries, the
sample, and the cell's rehearsal."""

import inspect
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import test_bench_manifest as held
from benchmarks import manifest, roofline_window_full as rl
from benchmarks import weights_mimo_v2 as wts
from benchmarks.planes import serve_window_full as plane
from benchmarks.reference import mimo_v2 as ref

NAME, CELL, MIX = ("mimo-v2.5-l7-ep16", "mimo25_mixed_closed_8k",
                   "mixed_closed_8k_a512")
CFG = manifest.config(NAME)
TINY = {**CFG["model"], **CFG["rehearsal"]["model"],
        "num_hidden_layers": 7}
TDEP = {**CFG["deployment"], **CFG["rehearsal"]["deployment"]}
STD = dict(std=0.2, sink_std=1.0)
NEW_METRICS = ("gqa_full_decode_roofline", "gqa_window_decode_roofline",
               "window_full_attn_device_share", "window_full_prefill_attn_ms",
               "ep16_moe_routed_roofline", "window_full_cache_bytes_live")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _reference_logits(toks, seed, dtype=jnp.float32):
    ends = wts.ends(TINY, seed, dtype, std=STD["std"])
    x = ref.embed(jnp.asarray(toks), ends["tok_emb"])
    for l in range(TINY["num_hidden_layers"]):
        x = ref.layer(x, wts.layer(TINY, TDEP, seed, l, dtype, **STD),
                      qblock=8,
                      **ref.layer_kwargs(TINY, l, TDEP["expert_offset"]))
    return np.asarray(ref.head(x, ends["final_norm"], ends["lm_head"],
                               TINY["layernorm_epsilon"]))


def _system_logits(toks, seed, dtype, control=None, params=None):
    from ray_tpu.models import mimo_v2

    mcfg = plane.model_config(TINY, TDEP, dtype, control)
    if params is None:
        params = wts.params(TINY, TDEP, seed, dtype, **STD)
    return np.asarray(mimo_v2.forward(
        mcfg, params, jnp.asarray(toks)[None])[0][0])


def _toks(seed=4, T=48):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], size=T)


# ----------------------------------------------------------------------
def test_the_reference_imports_nothing_from_the_program():
    assert "ray_tpu" not in inspect.getsource(ref).replace(
        "nothing from `ray_tpu`", "")
    assert "mimo_v2" not in "".join(
        l for l in inspect.getsource(ref).splitlines() if "import" in l)


def test_the_configuration_copies_the_catalog_and_lists_its_cuts():
    m = CFG["model"]
    assert all(CFG[k] == v for k, v in m.items())       # the two copies
    cut = {"num_hidden_layers": 7, "n_routed_experts": 16,
           "vocab_size": 19072,
           "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
           "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1]}
    assert {k: m[k] for k in cut} == cut
    assert sorted(CFG["reduced"]) == sorted(cut)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(l) for l in f
                       if l.startswith('{"name": "MiMo-V2.5"'))
        assert CFG["source"] == row["source_url"]
        assert {k: v for k, v in m.items() if k not in cut} == \
            {k: v for k, v in row["config"].items() if k not in cut}
        for k in ("hybrid_layer_pattern", "moe_layer_freq"):
            assert row["config"][k][:7] == m[k]
        pub = CFG["published"]
        assert all(row["config"][k] == pub[k] for k in (
            "num_hidden_layers", "n_routed_experts", "vocab_size"))
    # no width is cut
    for k in ("hidden_size", "head_dim", "v_head_dim", "swa_head_dim",
              "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "sliding_window"):
        assert k not in CFG["reduced"]
    for k in ("initializer_range", "sink", "route_eps", "sliding_window",
              "hybrid_layer_pattern", "rope", "towers"):
        assert k in CFG["assumed"], k
    dep = CFG["deployment"]
    assert (dep["chips_per_layer"], dep["router_experts"],
            dep["experts_per_chip"], dep["vocab_shards"]) == (16, 256, 16, 8)
    assert dep["experts_per_chip"] * dep["chips_per_layer"] == \
        CFG["published"]["n_routed_experts"]
    assert m["vocab_size"] * dep["vocab_shards"] == \
        CFG["published"]["vocab_size"]
    # the floors: a whole period and >= 4 layers after the dense one,
    # >= 8 experts, >= 1/8 of the vocabulary
    assert sum(m["moe_layer_freq"]) >= 4 and m["n_routed_experts"] >= 8
    assert m["hybrid_layer_pattern"][1:].count(0) == 1


def test_the_cut_weighs_what_the_file_says():
    """6.86 GB of bfloat16 weights, counted from the shapes; the cache
    beside them."""
    m, dep, e = CFG["model"], CFG["deployment"], CFG["engine"]
    count = lambda shp: sum(int(np.prod(s)) for s in shp.values())  # noqa: E731
    layers = [count(wts.shapes(m, dep, wts.kind_of(m, l)))
              for l in range(m["num_hidden_layers"])]
    ends = 2 * m["vocab_size"] * m["hidden_size"] + m["hidden_size"]
    assert [round(n / 1e6, 1) for n in layers] == [
        290.5, 498.1, 498.1, 498.1, 498.1, 492.8, 498.1]
    total = sum(layers) + ends
    assert 3.42e9 < total < 3.44e9 and 6.85e9 < 2 * total < 6.87e9
    per_token = 2 * 4 * (192 + 128) * 2
    per_slot = 5 * 128 * 8 * (192 + 128) * 2
    assert (per_token, per_slot) == (5120, 3276800)
    pool = (e["kv_blocks"] + 1) * e["block_size"] * per_token
    assert 3.35e9 < pool < 3.36e9 and e["slots"] * per_slot == 419430400
    # over half of 16 GB before any activation
    assert 2 * total + pool + e["slots"] * per_slot > 0.6 * 16e9
    mix = manifest.traffic(MIX)
    longest = max(mix["prompt_len"]["choices"]) + mix["output_len"]["fixed"]
    assert e["max_len"] >= longest + e["block_size"]
    assert e["prefill_chunk"] % e["block_size"] == 0
    # every prompt is whole blocks, the long ones whole chunks; the
    # short ones are packed, two a program at most
    for p in mix["prompt_len"]["choices"]:
        assert p % e["block_size"] == 0
        assert p <= e["prefill_chunk"] or p % e["prefill_chunk"] == 0
    # paged in every layer the live batch would not fit
    everywhere = 2 * 2560 + 5 * 5120
    assert everywhere == 30720 and 490_000 * everywhere > 15e9


def test_layer_from_seed_equals_the_tree():
    params = wts.params(TINY, TDEP, 9, jnp.float32, **STD)
    for l in (0, 1, 5):
        again = wts.layer(TINY, TDEP, 9, l, jnp.float32, **STD)
        assert set(again) == set(params["layers"][l])
        for k, v in again.items():
            assert np.array_equal(np.asarray(v),
                                  np.asarray(params["layers"][l][k])), (l, k)
    assert params["layers"][1]["router"].shape[-1] == TDEP["router_experts"]
    assert params["layers"][1]["e_gate"].shape[0] == TINY["n_routed_experts"]
    # a window layer holds sinks of order one, a full layer none
    assert "sink" in params["layers"][1] and "sink" not in params["layers"][5]
    assert 0.3 < float(jnp.std(params["layers"][1]["sink"])) < 3.0
    assert "w_gate" in params["layers"][0]


def test_the_system_equals_the_reference_float32():
    toks = _toks()
    assert len(toks) > 3 * TINY["sliding_window"]
    want = _reference_logits(toks, 4)
    got = _system_logits(toks, 4, jnp.float32)
    assert np.abs(got - want).max() < 2e-4
    assert want.std() > 0.3


@pytest.mark.parametrize("control", plane.CONTROLS)
def test_a_control_is_another_result(control):
    """Each control moves the logits of a context where its mechanism
    binds by far more than float32 against float32 differs."""
    toks = _toks()
    want = _reference_logits(toks, 4)
    params = wts.params(TINY, TDEP, 4, jnp.float32, **STD)
    if control == "fp8":
        from benchmarks.planes.serve_sparse_latent import fp8_weights

        params = fp8_weights(params)
        for k in ("router", "sink"):
            assert np.array_equal(
                np.asarray(params["layers"][1][k]),
                np.asarray(wts.layer(TINY, TDEP, 4, 1, jnp.float32,
                                     **STD)[k]))
    got = _system_logits(toks, 4, jnp.float32,
                         None if control == "fp8" else control, params)
    assert np.abs(got - want)[-8:].max() > 1e-2
    mcfg = plane.model_config(TINY, TDEP, jnp.float32, control)
    assert mcfg.window == TINY["sliding_window"] * (
        plane.WINDOW_OFF_FACTOR if control == "window_off" else 1)
    assert mcfg.swa_sink == (control != "sink_off")


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="controls are"):
        plane.BenchWindowFullService(
            {**CFG, "model": TINY, "deployment": TDEP}, {}, 1,
            {"bench_dir": "/nonexistent", "rehearse": True,
             "control": "int8"})


def test_the_traffic_is_the_issues():
    mix = manifest.traffic(MIX)
    assert (mix["kind"], mix["clients"], mix["mix_seed"]) == \
        ("closed_loop", 192, 2407)
    assert mix["prompt_len"] == {"choices": [1024, 4096, 8192],
                                 "weights": [2, 1, 1]}
    assert "shared_prefix" not in mix
    assert mix["first_output_step"] == 16 and mix["drain_s"] == 30.0
    assert mix["output_len"]["fixed"] in (512, 256)   # or the named fallback
    assert mix["trace_s"] == 3.0 and mix["requests_per_client"] >= 8
    assert CFG["engine"]["slots"] == 128


# -- the rooflines' counts, by hand at one shape ------------------------
def test_roofline_counts_at_the_cells_shapes():
    peaks = manifest.peaks("TPU v5 lite")
    rows, live = 115, 490_000
    f = rl.gqa_decode(live, rows, 64, 4, 192, 128)
    assert f["bytes"] == live * 2560 + rows * 64 * 320 * 2
    assert f["flops"] == 2 * 64 * 320 * live
    # 1.254 GB + 4.7 MB: 1.54 ms; 20 GFLOP: 0.10 ms
    assert rl.least_seconds(f, peaks)["bound"] == "memory"
    assert 1.52e-3 < rl.least_seconds(f, peaks)["seconds"] < 1.56e-3
    w = rl.gqa_decode(rows * 128, rows, 64, 8, 192, 128)
    assert w["bytes"] == rows * 128 * 5120 + rows * 64 * 320 * 2
    # 75.4 MB + 4.7 MB: 0.098 ms
    assert 0.095e-3 < rl.least_seconds(w, peaks)["seconds"] < 0.101e-3
    e = rl.ep_moe_routed(rows, 8, 16, 256, 6 * 15.5, 6, 4096, 2048)
    expert = 3 * 4096 * 2048 * 2
    assert expert == 50_331_648
    assert 0.995 < (93 * expert) / e["bytes"] <= 1.0
    assert e["flops"] == 2 * 3 * 4096 * 2048 * rows * 8 * 16 / 256 * 6
    # 4.68 GB of held experts: 5.7 ms
    assert 5.6e-3 < rl.least_seconds(e, peaks)["seconds"] < 5.8e-3
    assert rl.cache_bytes(live, rows, 5120, 3276800) == \
        live * 5120 + rows * 3276800


# -- the readers ---------------------------------------------------------
def _ctx(scopes=None, ticks=(), prefill=None, kernels=None):
    engine = {"request_ring": [], "cache_bytes_per_token": 5120,
              "cache_bytes_per_slot": 3276800}
    trace = {"devices": 1, "scopes": scopes or {}}
    if prefill:
        trace["prefill_scopes"] = prefill
    if kernels:
        trace["kernels"] = kernels
    return {"plane": "serve", "config": CFG, "setup_s": 10.5, "seconds": 4.0,
            "peaks": manifest.peaks("TPU v5 lite"),
            "client": {"per_replica": {"1": 0}},
            "replicas": [{"rid": "1", "tick_ring": list(ticks),
                          "engine": engine, "trace": trace}]}


def test_every_new_reader_reads_a_recorded_context(monkeypatch):
    monkeypatch.setenv("RT_BENCH_T0", "1000.0")
    tick = {"t_wall": 1011.2, "active": 120, "live_tokens": 490_000,
            "state_rows_live": 115, "row_steps": 1024, "row_steps_live": 920,
            "experts_touched": 93.0, "experts_total": 96,
            "expert_load_max": 12, "experts_held": 16,
            "window_rows_live": 115 * 128, "ring_bytes_live": 115 * 3276800,
            "full_cache_tokens_live": 490_000}
    # 10 programs of 8 steps: 80 steps; 2 full and 5 window layers
    scopes = {"programs_s": 2.0, "program_calls": 10, "full_attn": 0.64,
              "swa_attn": 0.16, "swa_ring_write": 0.04, "moe_routed": 0.6,
              "moe_router": 0.02, "dense_mlp": 0.08}
    prefill = {"programs_s": 0.9, "program_calls": 30, "full_attn": 0.09,
               "swa_attn": 0.03, "moe_routed": 0.3}
    kernels = {"paged_decode": {"seconds": 2.0, "calls": 10,
                                "op_seconds": 0.56, "op_calls": 160}}
    # set-up's ticks (before 1010.5) and the drain's are not the window's
    warm = {**tick, "t_wall": 1003.0, "full_cache_tokens_live": 9,
            "live_tokens": 9}
    late = {**tick, "t_wall": 1014.6, "full_cache_tokens_live": 9}
    ctx = _ctx(scopes, [warm, tick, {**tick, "t_wall": 1012.9},
                        {"t_wall": 1013.0, "active": 0, "live_tokens": 0},
                        late], prefill, kernels)
    read = lambda n: manifest.layer_metric(n).read(ctx)  # noqa: E731
    # 0.56 s / 160 calls = 3.5 ms against 1.54 ms
    assert 43 < read("gqa_full_decode_roofline") < 45
    # 0.16 s / (80 x 5) = 0.4 ms against 0.098 ms
    assert 23.5 < read("gqa_window_decode_roofline") < 25.5
    assert read("window_full_attn_device_share") == pytest.approx(40.0)
    assert read("window_full_prefill_attn_ms") == pytest.approx(4.0)
    # 0.6 s / 80 = 7.5 ms against 5.7 ms
    assert 75 < read("ep16_moe_routed_roofline") < 77.5
    assert read("window_full_cache_bytes_live") == pytest.approx(
        490_000 * 5120 + 115 * 3276800)
    for name in NEW_METRICS:
        v = read(name)
        assert v is not None and (v <= 100 or "roofline" not in name), name
    assert manifest.layer_metric("decode_step_ms").read(ctx) == \
        pytest.approx(1e3 * 2.0 / 80)


def test_the_new_readers_read_nothing_on_another_cell_or_the_parent():
    """A cell of another model, and this cell on a program without the
    scopes, the counters or the kernel: None, never an error."""
    lfm2 = {**_ctx({"programs_s": 1.2, "program_calls": 10,
                    "moe_routed": 0.8},
                   [{"active": 64, "live_tokens": 64 * 700,
                     "state_rows_live": 60, "experts_touched": 200.0,
                     "experts_total": 448, "expert_load_max": 9,
                     "row_steps": 1024, "row_steps_live": 500}],
                   {"program_calls": 4, "moe_routed": 0.1},
                   {"paged_decode": {"seconds": 1.2, "calls": 10,
                                     "op_seconds": 0.1, "op_calls": 320}}),
            "config": manifest.config("lfm2-8b-a1b-l16")}
    parent = _ctx({}, [{"active": 120, "live_tokens": 1000,
                        "state_rows_live": 100, "row_steps_live": 800}])
    os.environ["RT_BENCH_T0"] = "1000.0"
    try:
        for name in NEW_METRICS:
            for ctx in (lfm2, parent, {"plane": "train"}):
                assert manifest.layer_metric(name).read(ctx) is None, name
    finally:
        del os.environ["RT_BENCH_T0"]


def test_the_kernel_is_found_by_what_it_returns():
    pred = plane.kernel_predicates(CFG)
    attn = ('%closed_call.3 = bf16[128,64,512]{2,1,0} custom-call(...), '
            'custom_call_target="tpu_custom_call"')
    other = attn.replace("bf16[128,64,512]", "bf16[128,32,512]")
    assert pred["paged_decode"](attn) and not pred["paged_decode"](other)
    assert pred["paged_append"](
        'x = (bf16[2,40961,16,768]) custom-call(...), custom_call_target='
        '"tpu_custom_call", output_to_operand_aliasing={...}')


def _served(lengths=(1024, 4096, 8192), span=512, shorts=20, fulls=9):
    """A window's answers as the replica keeps them: behind each prompt
    length `shorts` callers' first answers of 16..496 tokens and `fulls`
    of full length."""
    rng = np.random.default_rng(5)
    out = []
    for n in lengths:
        for j in range(shorts):
            out.append(([7] * n, [3] * (16 * (1 + j % 31))))
        for _ in range(fulls):
            out.append(([7] * n, [4] * span))
    return [out[i] for i in rng.permutation(len(out))]


@pytest.mark.parametrize("seed", [0, 7, 5100000101, 2**31 + 11])
def test_the_sample_is_full_answers_over_every_prompt_length(seed):
    served = _served()
    pick = plane.sample_answers(served, 8, 512, seed)
    assert len(pick) == len(set(pick)) == 8
    assert [len(served[i][1]) for i in pick] == [512] * 8
    assert {len(served[i][0]) for i in pick} == {1024, 4096, 8192}
    assert pick == plane.sample_answers(served, 8, 512, seed)
    assert sorted(plane.sample_answers(served, 8, 512, seed + 1)) != \
        sorted(pick)                                   # the seed draws
    # a length with no full answer falls back to a shorter one
    few = [s for s in served if len(s[0]) != 8192 or len(s[1]) < 512]
    lens = {len(few[i][0]): len(few[i][1])
            for i in plane.sample_answers(few, 3, 512, seed)}
    assert lens[1024] == lens[4096] == 512 and lens[8192] < 512


# -- the manifest ---------------------------------------------------------
# the closed cells that stood in the shared lists before this one
BEFORE = ["mistral7b_batch_closed", "kanana2_batch_closed_1k",
          "brumby14b_batch_closed_1k", "lfm2_batch_closed_512",
          "dots3_docqa_closed_16k"]


def check_the_manifest_finds_every_new_file():
    """What PR 51 added is held BY NAME: where in its list an entry
    stands, and what follows it, is the next PR's to change
    (`test_bench_manifest.py::test_a_list_can_grow` runs this against a
    manifest that grew)."""
    man = manifest.manifest()
    cell = manifest.cell(CELL)
    assert cell in man["workloads"] and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (NAME, MIX)
    entry = next(c for c in man["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"]
    assert os.path.exists(os.path.join(manifest.REPO, entry["file"]))
    assert os.path.exists(os.path.join(manifest.REPO,
                                       CFG["reference"]["file"]))
    assert CFG["plane"] == "serve_window_full"
    e2e = [e["name"] for e in manifest.metrics_for(CELL, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    per_layer = manifest.metrics_for(CELL, "per_layer")
    names = [p["name"] for p in per_layer]
    # the new entries are all listed, in the issue's order among
    # themselves, wherever they stand
    listed = [p["name"] for p in man["per_layer"] if p["name"] in NEW_METRICS]
    assert tuple(listed) == NEW_METRICS
    assert [n for n in names if n in NEW_METRICS] == list(NEW_METRICS)
    # at least the sixteen shared readers of a closed cell, and none
    # that counts another model's widths
    assert held.CLOSED_SHARED <= set(names)
    assert not {"mla_decode_roofline", "moe_routed_roofline",
                "paged_decode_roofline", "hybrid_paged_decode_roofline",
                "swa_decode_roofline"} & set(names)
    for p in per_layer:
        mod = manifest.layer_metric(p["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            p["layer"], p["unit"], p["source"], p["moves"]), p["name"]
        assert p["moves"] == "serve_tokens_per_s"
    for p in man["per_layer"] + man["end_to_end"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL]
        elif "workloads" in p and (p["name"] in held.CLOSED_SHARED
                                   or p["name"] == "serve_tokens_per_s"):
            # appended: every cell that stood there still stands before it
            # (a shared entry with no list, `serve_mfu`, has nothing to
            # append to)
            at = p["workloads"].index(CELL)
            assert p["workloads"][:at] == BEFORE, p["name"]


def test_the_manifest_finds_every_new_file():
    check_the_manifest_finds_every_new_file()


def test_the_grown_manifest_passes_the_checks_that_hold_earlier_entries():
    """`test_a_list_can_grow`'s checks, on the manifest as this PR
    leaves it."""
    import test_bench_hybrid as hybrid

    hybrid.check_the_manifest_finds_every_new_file()
    held.check_every_cell_reports_enough_and_uses_a_known_config(
        manifest.manifest())


def test_the_parent_fails_at_once_on_the_missing_model(monkeypatch):
    monkeypatch.setattr(plane, "NEED", (("models", "no_such_model.py"),))
    with pytest.raises(RuntimeError, match="cannot run"):
        plane.run({"name": CELL}, CFG, {}, None, 0.0)


# The closed mix's row stands in `closed_sizes/mixed_closed_8k_a512.json`
# (PR 54): the set check of `test_bench_manifest.py` holds it against the
# mix's file and this configuration's slots, and the three checks that
# directory parametrises take it as a case.


def test_the_cells_rehearsal_leaves_nothing_running():
    import test_bench_guard as guard

    proc, mark = guard.start(CELL)
    out, err = proc.communicate(timeout=900)
    assert proc.returncode == 3, err[-3000:]
    assert "rehearsal passed" in err
    assert '"correct"' not in out.strip().splitlines()[-1]
    assert '"metrics"' not in out
    # the rows of `correct` are the run's last lines on standard error
    assert '"mean_margin_below_reference_argmax"' in err
    guard.assert_clean(mark)
