"""The block-diffusion configuration (`sdar-30b-a3b-chat-l6`) and its
cell: the configuration's file against the catalog, the cut's bytes
recomputed, the seeded weights, the system against the plain reference
at the rehearsal's widths, the rooflines' counts by hand, every new
reader on a recorded context and on another cell's, the sample, the
manifest's entries BY NAME, and the cell's rehearsal."""

import inspect
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import test_bench_manifest as held
from benchmarks import manifest, roofline_block_diffusion as rl
from benchmarks import weights_sdar as wts
from benchmarks.planes import serve_block_diffusion as plane
from benchmarks.reference import sdar as ref

NAME, CELL, MIX = ("sdar-30b-a3b-chat-l6", "sdar30b_blockgen_closed_512",
                   "blockgen_closed_512_a256")
CFG = manifest.config(NAME)
TINY = {**CFG["model"], **CFG["rehearsal"]["model"]}
TASSUMED = {**CFG["assumed"], **CFG["rehearsal"]["assumed"]}
NEW_METRICS = ("blockgen_tokens_per_row_forward",
               "blockgen_commit_forward_share", "blockgen_block_attn_roofline",
               "blockgen_moe_routed_roofline", "blockgen_head_confidence_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_reference_imports_nothing_from_the_program():
    assert "ray_tpu" not in inspect.getsource(ref).replace(
        "nothing from `ray_tpu`", "")


def test_the_configuration_copies_the_catalog_and_lists_its_one_cut():
    m = CFG["model"]
    assert all(CFG[k] == v for k, v in m.items())       # the two copies
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert m["num_hidden_layers"] == 6
    assert CFG["published"]["num_hidden_layers"] == 48
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(l) for l in f
                       if l.startswith('{"name": "SDAR-30B-A3B-Chat"'))
        assert CFG["source"] == row["source_url"]
        assert {**m, "num_hidden_layers": 48} == row["config"]
    for k in ("block_length", "mask_id", "no_shift", "head_norms",
              "remasking_strategy", "confidence_threshold",
              "denoising_steps", "initializer_range", "rope", "router"):
        assert k in CFG["assumed"], k
    for k in ("block_length", "mask_id", "denoising_steps",
              "initializer_range"):
        assert CFG["assumed"][k + "_why"]
    dep = CFG["deployment"]
    assert (dep["chips_per_layer"], dep["experts_per_chip"],
            dep["expert_offset"]) == (1, m["num_experts"], 0)
    # the floors: four layers, eight experts, the vocabulary whole
    assert m["num_hidden_layers"] >= 4 and m["num_experts"] >= 8
    assert CFG["assumed"]["mask_id"] < m["vocab_size"]


def test_the_cut_weighs_what_the_file_says():
    """`reduced_why`'s arithmetic, recomputed from the shapes: 8.72 GB
    of bfloat16 weights, 12,288 B a cached token, a pool of 2.04 GB:
    63% of the chip before activations."""
    m, e = CFG["model"], CFG["engine"]
    count = lambda shp: sum(int(np.prod(s)) for s in shp.values())  # noqa: E731
    layer = count(wts.shapes(m))
    ends = 2 * m["vocab_size"] * m["hidden_size"] + m["hidden_size"]
    attn = (m["hidden_size"] * (32 + 4 + 4) * 128 + 32 * 128 * 2048)
    assert (attn, layer, ends) == (18_874_368, 623_120_640, 622_331_904)
    total = m["num_hidden_layers"] * layer + ends
    assert total == 4_361_055_744
    per_token = m["num_hidden_layers"] * 2 * 4 * 128 * 2
    pool = (e["kv_blocks"] + 1) * e["block_size"] * per_token
    assert per_token == 12_288 and 2.03e9 < pool < 2.05e9
    for n in ("18,874,368", "623,120,640", "622,331,904", "4,361,055,744",
              "12,288"):
        assert n in CFG["reduced_why"], n
    share = (2 * total + pool) / 17.2e9
    assert 0.55 < share < 0.70 and "63%" in CFG["reduced_why"]
    mix = manifest.traffic(MIX)
    longest = max(mix["prompt_len"]["choices"]) + mix["output_len"]["fixed"]
    assert e["max_len"] == longest + e["block_size"]
    B = CFG["assumed"]["block_length"]
    assert e["block_size"] % B == 0 and e["max_len"] % B == 0
    assert e["kv_blocks"] >= e["slots"] * -(-longest // e["block_size"])
    for p in mix["prompt_len"]["choices"]:
        assert p % e["block_size"] == 0


def test_the_traffic_is_the_issues():
    mix = manifest.traffic(MIX)
    assert (mix["kind"], mix["clients"], mix["first_output_step"]) == (
        "closed_loop", 192, 16)
    assert mix["prompt_len"] == {"choices": [256, 512, 1024],
                                 "weights": [2, 2, 1]}
    assert mix["output_len"] == {"fixed": 256}
    assert mix["request_fields"] == {"denoising_steps": {
        "choices": [1, 2, 4], "weights": [1, 2, 1]}}
    assert mix["requests_per_client"] == 24 and mix["drain_s"] == 30.0
    plan = held.loadgen.closed_loop_schedule(mix, 7, 1000)
    steps = [r.fields["denoising_steps"] for p in plan for r in p]
    share = {s: steps.count(s) / len(steps) for s in (1, 2, 4)}
    assert set(steps) == {1, 2, 4} and abs(share[2] - 0.5) < 0.03
    # 2 / 3 / 5 forwards a block of 4: tokens a row-forward over the mix
    per = 4 / sum(share[s] * (s + 1) for s in share)
    assert per == pytest.approx(1.23, abs=0.03)
    assert json.loads(held.loadgen.body_of(plan[0][0]))[
        "denoising_steps"] in (1, 2, 4)


def test_layer_from_seed_equals_the_tree_and_the_system_the_reference():
    from ray_tpu.models import sdar

    std = TASSUMED["initializer_range"]
    params = wts.params(TINY, 9, jnp.float32, std)
    for l in (0, 2):
        again = wts.layer(TINY, 9, l, jnp.float32, std)
        assert set(again) == set(wts.LEAVES)
        for k, v in again.items():
            assert np.array_equal(np.asarray(v),
                                  np.asarray(params["layers"][k][l])), (l, k)
    assert params["layers"]["router"].dtype == jnp.float32
    mcfg = plane.model_config(TINY, TASSUMED, jnp.float32)
    assert (mcfg.block_length, mcfg.mask_id, mcfg.n_experts, mcfg.top_k) == (
        4, 255, 8, 2)
    toks = np.random.default_rng(4).integers(1, 255, size=24)
    ends = wts.ends(TINY, 9, jnp.float32, std)
    layers = [wts.layer(TINY, 9, l, jnp.float32, std) for l in range(3)]
    want = np.asarray(ref.forward(jnp.asarray(toks), 4, ends, layers,
                                  ref.layer_kwargs(TINY)))
    got = np.asarray(sdar.forward(mcfg, params, jnp.asarray(toks)[None])[0][0])
    # float32 on both sides: summation order alone (test_sdar_model.TOL)
    assert np.abs(got - want).max() < 2e-4
    # the fp8 control rounds the layers' matrices and nothing else
    low = plane.fp8_weights(params)
    for k in wts.LEAVES:
        same = np.array_equal(np.asarray(low["layers"][k]),
                              np.asarray(params["layers"][k]))
        assert same == (k not in wts.MATRICES), k
    assert low["lm_head"] is params["lm_head"]


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="controls are"):
        plane.BenchBlockDiffusionService(
            {**CFG, "model": TINY, "assumed": TASSUMED}, {}, 1,
            {"bench_dir": "/nonexistent", "rehearse": True,
             "control": "int8"})


def test_roofline_counts_at_the_cells_shapes():
    # one layer's attention of a forward: 128 live rows of 4 positions
    # over 100,000 cached columns
    w = rl.block_attention(100_000, 128, 4, 32, 4, 128)
    kv_row = 2 * 4 * 128 * 2
    assert w["bytes"] == (100_000 * kv_row + 128 * 4 * kv_row
                          + 2 * 128 * 4 * 32 * 128 * 2)
    assert w["flops"] == 4 * 32 * 128 * 4 * 100_000
    peaks = manifest.peaks("TPU v5 lite")
    assert rl.least_seconds(w, peaks)["bound"] == "memory"
    # a forward's experts: 4,096 pairs a layer, 760 of 768 touched
    e = rl.moe_routed(4096, 760, 6, 2048, 768)
    assert e["bytes"] == 760 * 3 * 2048 * 768 * 2 + 6 * 4096 * (
        2 * 2048 + 768 + 2048) * 2
    assert rl.least_seconds(e, peaks)["bound"] == "memory"


def _ctx(ticks=(), scopes=None, kernels=None, span=None):
    trace = {"devices": 1, "scopes": scopes or {}, "kernels": kernels or {}}
    if span:
        trace["wall_span"] = span
    return {"plane": "serve", "config": CFG, "cell": manifest.cell(CELL),
            "peaks": manifest.peaks("TPU v5 lite"),
            "replicas": [{"tick_ring": list(ticks), "trace": trace}]}


def _tick(t_wall, commit=120, denoise=270, attended=100_000.0, touched=760.0):
    return {"t_wall": t_wall, "row_steps": 1024,
            "row_steps_live": commit + denoise, "commit_row_steps": commit,
            "denoise_row_steps": denoise, "tokens_committed": 4 * commit,
            "attended_tokens": attended, "experts_touched": touched,
            "experts_total": 768, "expert_load_max": 60}


def test_every_new_reader_reads_a_recorded_context():
    read = {n: manifest.layer_metric(n).read for n in NEW_METRICS}
    ticks = [_tick(10.0), _tick(20.0, attended=50_000.0), _tick(30.0)]
    ctx = _ctx(ticks)
    assert read["blockgen_tokens_per_row_forward"](ctx) == pytest.approx(
        480 / 390)
    assert read["blockgen_commit_forward_share"](ctx) == pytest.approx(
        100 * 120 / 390)
    # nothing traced: the trace's readers say nothing
    for n in NEW_METRICS[2:]:
        assert read[n](ctx) is None
    kernels = {"paged_decode": {"seconds": 0.13, "calls": 10,
                                "op_seconds": 0.24, "op_calls": 480}}
    scopes = {"program_calls": 10, "programs_s": 1.3, "moe_routed": 0.8,
              "lm_head": 0.16, "unmask": 0.08}
    # the counts come from the tick INSIDE the traced span (the second)
    ctx = _ctx(ticks, scopes, kernels, span=[19.0, 22.0])
    rows = 390 / 8
    want = rl.share(rl.block_attention(50_000.0, rows, 4, 32, 4, 128),
                    0.24 / 480, ctx["peaks"])
    assert read["blockgen_block_attn_roofline"](ctx) == pytest.approx(want)
    assert 0 < want < 100
    want = rl.share(rl.moe_routed(rows * 4 * 8, 760.0, 6, 2048, 768),
                    0.8 / 80, ctx["peaks"])
    assert read["blockgen_moe_routed_roofline"](ctx) == pytest.approx(want)
    assert 0 < want < 100
    assert read["blockgen_head_confidence_ms"](ctx) == pytest.approx(
        1e3 * 0.24 / 80)
    # no tick began inside the span: the nearest one's counts
    ctx = _ctx(ticks, scopes, kernels, span=[11.0, 12.0])
    want = rl.share(rl.block_attention(100_000.0, rows, 4, 32, 4, 128),
                    0.24 / 480, ctx["peaks"])
    assert read["blockgen_block_attn_roofline"](ctx) == pytest.approx(want)


def test_the_new_readers_read_nothing_on_another_cell_or_the_parent():
    other = manifest.config("mimo-v2.5-l7-ep16")
    ticks = [_tick(10.0)]
    for n in NEW_METRICS:
        read = manifest.layer_metric(n).read
        # another model's configuration; the train plane; a parent whose
        # ring has no such counter and whose trace no such scope
        assert read({**_ctx(ticks), "config": other}) is None
        assert read({"plane": "train", "config": CFG}) is None
        bare = [{"t_wall": 10.0, "row_steps": 1024, "row_steps_live": 900}]
        assert read(_ctx(bare, {"program_calls": 3, "programs_s": 1.0})) \
            is None


def test_the_kernel_is_found_by_what_it_returns():
    pred = plane.kernel_predicates(CFG)
    call = ('%x = bf16[128,128,512]{2,1,0} custom-call(...), '
            'custom_call_target="tpu_custom_call"')
    assert pred["paged_decode"](call)
    assert not pred["paged_decode"](call.replace("[128,128,512]",
                                                 "[128,32,512]"))
    append = ('%y = (bf16[6,10369,16,512], bf16[6,10369,16,512]) '
              'custom-call(...), custom_call_target="tpu_custom_call", '
              'output_to_operand_aliasing={...}')
    assert pred["paged_append"](append) and not pred["paged_decode"](append)


@pytest.mark.parametrize("seed", [0, 5500000101, 2**31 + 11])
def test_the_sample_covers_every_length_and_every_step_count(seed):
    rng = np.random.default_rng(1)
    served = []
    for _ in range(120):
        T, S = int(rng.choice([256, 512, 1024])), int(rng.choice([1, 2, 4]))
        n = 256 if rng.random() < 0.8 else 16 * int(rng.integers(1, 16))
        served.append(([0] * T, [0] * n, [0] * n, S))
    pick = plane.sample_answers(served, 9, 256, seed)
    assert len(pick) == len(set(pick)) == 9
    assert {(len(served[i][0]), served[i][3]) for i in pick} == {
        (T, S) for T in (256, 512, 1024) for S in (1, 2, 4)}
    assert all(len(served[i][1]) == 256 for i in pick)   # full answers first
    assert pick == plane.sample_answers(served, 9, 256, seed)


def test_the_verdict_holds_the_choice_margins_too():
    check = {"sampled": 9, "tokens": 2304, "mean_margin": 0.01,
             "max_margin": 0.5, "choices": 800, "mean_choice_margin": 0.01,
             "max_choice_margin": 0.4}
    ctx = {"replicas": [{"check": check}], "client": {"cut_at_end": 0},
           "traffic": {"kind": "closed_loop"}}
    lim = {"reference": {"mean_margin_limit": 0.1, "max_margin_limit": 1.0,
                         "min_tokens": 1536, "mean_choice_margin_limit": 0.1,
                         "max_choice_margin_limit": 1.0}}
    v = plane.verdict(ctx, lim)
    assert v["correct"] and len(v["rows"]) == 6
    check["mean_choice_margin"] = 0.2
    v = plane.verdict(ctx, lim)
    assert not v["correct"]
    assert [r[0] for r in v["rows"] if not r[1] <= r[2]] == [
        "mean_choice_margin_below_reference"]


# -- the manifest ---------------------------------------------------------
# the closed cells that stood in the shared lists before this one
BEFORE = ["mistral7b_batch_closed", "kanana2_batch_closed_1k",
          "brumby14b_batch_closed_1k", "lfm2_batch_closed_512",
          "dots3_docqa_closed_16k", "mimo25_mixed_closed_8k"]


def check_the_manifest_finds_every_new_file():
    """What PR 55 added is held BY NAME: where in its list an entry
    stands, and what follows it, is the next PR's to change
    (`test_bench_manifest.py::test_a_list_can_grow` runs this against a
    manifest that grew)."""
    man = manifest.manifest()
    cell = manifest.cell(CELL)
    assert cell in man["workloads"] and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (NAME, MIX)
    entry = next(c for c in man["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    assert os.path.exists(os.path.join(manifest.REPO, entry["file"]))
    assert os.path.exists(os.path.join(manifest.REPO,
                                       CFG["reference"]["file"]))
    assert CFG["plane"] == "serve_block_diffusion"
    e2e = [e["name"] for e in manifest.metrics_for(CELL, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    per_layer = manifest.metrics_for(CELL, "per_layer")
    names = [p["name"] for p in per_layer]
    listed = [p["name"] for p in man["per_layer"] if p["name"] in NEW_METRICS]
    assert tuple(listed) == NEW_METRICS
    assert [n for n in names if n in NEW_METRICS] == list(NEW_METRICS)
    # at least the sixteen shared readers of a closed cell, and none
    # that counts another model's widths
    assert held.CLOSED_SHARED <= set(names)
    assert not {"mla_decode_roofline", "moe_routed_roofline",
                "paged_decode_roofline", "hybrid_paged_decode_roofline",
                "gqa_full_decode_roofline", "swa_decode_roofline"} & set(names)
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
    # the closed mix brings its row, and its request its field
    row = held.closed_sizes()[MIX]
    assert row["cell"] == CELL and row["slots"] == CFG["engine"]["slots"]
    assert "denoising_steps" in manifest.traffic(MIX)["request_fields"]


def test_the_manifest_finds_every_new_file():
    check_the_manifest_finds_every_new_file()


def test_the_grown_manifest_passes_the_checks_that_hold_earlier_entries():
    import test_bench_hybrid as hybrid
    import test_bench_window_full as window_full

    hybrid.check_the_manifest_finds_every_new_file()
    window_full.check_the_manifest_finds_every_new_file()
    held.check_every_cell_reports_enough_and_uses_a_known_config(
        manifest.manifest())
    held.check_the_mixes_in_the_table_are_the_closed_mixes()


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
    assert '"mean_margin_below_reference_argmax"' in err
    assert '"max_choice_margin_below_reference"' in err
    guard.assert_clean(mark)
