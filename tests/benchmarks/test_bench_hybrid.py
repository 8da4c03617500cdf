"""The hybrid configuration (LFM2-8B-A1B cut to 16 layers): the
configuration's file against the catalog row, the manifest's new
entries, the seeded weights, the system against its plain reference at
the rehearsal's sizes on the CPU, both controls, the counts of its
rooflines at the cell's shapes, its readers, and its cell's rehearsal."""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, roofline_hybrid
from benchmarks import weights_lfm2 as wts
from benchmarks.planes import serve_hybrid as plane
from benchmarks.reference import lfm2 as ref

NAME, CELL = "lfm2-8b-a1b-l16", "lfm2_batch_closed_512"
CFG = manifest.config(NAME)
REH = CFG["rehearsal"]
TINY = {**CFG["model"], **REH["model"]}
ASSUMED = {**CFG["assumed"], **REH["assumed"]}
LIMITS = REH["reference"]
NEW_METRICS = ("hybrid_moe_routed_roofline", "hybrid_paged_decode_roofline",
               "hybrid_moe_device_share", "short_conv_device_share",
               "hybrid_cache_bytes_live",
               "hybrid_moe_expert_load_max_over_mean")


def test_the_configuration_is_the_catalog_row_cut_in_depth_only():
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(json.loads(l) for l in f
                       if l.startswith('{"name": "LFM2-8B-A1B"'))
    m = CFG["model"]
    cut = ("num_hidden_layers", "layer_types")
    if row is not None:
        assert CFG["source"] == row["source_url"]
        assert {k: v for k, v in m.items() if k not in cut} == \
            {k: v for k, v in row["config"].items() if k not in cut}
        assert row["config"]["num_hidden_layers"] == 24
        assert m["layer_types"] == row["config"]["layer_types"][:16]
    assert m["num_hidden_layers"] == 16 and CFG["reduced"] == list(cut)
    assert m["layer_types"].count("conv") == 12
    assert m["layer_types"].count("full_attention") == 4
    assert all(CFG[k] == v for k, v in m.items())     # the top-level copy
    a, e = CFG["assumed"], CFG["engine"]
    # nothing the row does not give is a key of `model`
    assert a["head_dim"] == 64 and "head_dim" not in m
    assert a["tie_word_embeddings"] is True and a["route_eps"] == 1e-6
    assert a["initializer_range"] == 0.02
    assert (e["slots"], e["max_len"], e["chunk"], e["block_size"],
            e["kv_blocks"], e["prefix_cache"]) == (128, 1296, 8, 16, 10368,
                                                   False)
    # the arithmetic of `reduced_why`
    D, I, Im, E, V = 2048, 7168, 1792, 32, 65536
    conv = D * 3 * D + D * D + D * 3
    attn = 2 * D * D + 2 * D * 512
    dense, expert = 3 * D * I, 3 * D * Im
    moe = E * expert + D * E
    whole = 18 * conv + 6 * attn + 2 * dense + 22 * moe + V * D
    cut_params = 12 * conv + 4 * attn + 2 * dense + 14 * moe + V * D
    assert 8.33e9 < whole < 8.35e9 and 5.39e9 < cut_params < 5.41e9
    assert 10.79e9 < 2 * cut_params < 10.81e9 and 2 * whole > 16e9
    for text in ("10.80 GB", "8,192 B a token", "147 KB a slot"):
        assert text in CFG["reduced_why"], text
    shapes = wts.shapes(m, a)
    assert sum(int(np.prod(shapes[k])) for k in wts.LEAVES["conv"][1:]) == conv
    assert 3 * int(np.prod(shapes["e_gate"])) == E * expert
    mix = manifest.traffic("batch_closed_512_a128")
    # answers of 64: the issue's fallback from 128, for the cell's spread
    assert mix["clients"] == 192 and mix["output_len"] == {"fixed": 64}
    assert mix["prompt_len"] == {"choices": [256, 512, 1024],
                                 "weights": [2, 2, 1]}
    assert (mix["mix_seed"], mix["first_output_step"],
            mix["requests_per_client"], mix["drain_s"], mix["trace_s"]) == (
        2407, 8, 48, 30.0, 3.0)   # `drain_s`: a ceiling since PR 49
    assert max(mix["prompt_len"]["choices"]) + 64 < e["max_len"]
    assert all(p % e["block_size"] == 0 for p in mix["prompt_len"]["choices"])
    held = (e["kv_blocks"] + 1) * 16 * 8192 + e["slots"] * 147456
    assert 2 * cut_params + held > 0.25 * 16e9


def check_the_manifest_finds_every_new_file():
    """What a PR added is held BY NAME: where in its list an entry
    stands, and how many follow it, is the next PR's to change
    (`test_bench_manifest.py` runs this against a manifest that grew)."""
    man = manifest.manifest()
    cell = manifest.cell(CELL)
    assert cell in man["workloads"] and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (NAME, "batch_closed_512_a128")
    entry = next(c for c in man["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"]
    assert os.path.exists(os.path.join(manifest.REPO, entry["file"]))
    assert os.path.exists(os.path.join(manifest.REPO, CFG["reference"]["file"]))
    assert CFG["plane"] == "serve_hybrid"
    e2e = [e["name"] for e in manifest.metrics_for(CELL, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    per_layer = manifest.metrics_for(CELL, "per_layer")
    names = [p["name"] for p in per_layer]
    assert [n for n in names if n in NEW_METRICS] == list(NEW_METRICS)
    listed = [p for p in man["per_layer"] if p["name"] in NEW_METRICS]
    assert [p["name"] for p in listed] == list(NEW_METRICS)
    for p in per_layer:
        mod = manifest.layer_metric(p["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            p["layer"], p["unit"], p["source"], p["moves"]), p["name"]
        assert p["moves"] == "serve_tokens_per_s"
    for p in listed:
        assert p["workloads"] == [CELL]


def test_the_manifest_finds_every_new_file():
    check_the_manifest_finds_every_new_file()


def test_the_reference_imports_nothing_from_the_program():
    src = inspect.getsource(ref)
    assert "import ray_tpu" not in src and "from ray_tpu" not in src
    code = src.split('"""', 2)[2]
    # three shifted products, experts one at a time, no cache, no packing
    assert "jnp.pad(bu" in code and "scan(one" in code
    assert "cache" not in code and "seg" not in code


def test_layer_from_seed_equals_the_stacked_tree():
    tree = wts.params(TINY, ASSUMED, 9, jnp.float32)
    at = {k: 0 for k in wts.LEAVES}
    for l in range(TINY["num_hidden_layers"]):
        one = wts.layer(TINY, ASSUMED, 9, l, jnp.float32)
        kinds = wts.kinds_of(TINY, l)
        assert set(one) == set(wts.LEAVES[kinds[0]]) | set(wts.LEAVES[kinds[1]])
        for kind in kinds:
            for k in wts.LEAVES[kind]:
                np.testing.assert_array_equal(
                    np.asarray(one[k]), np.asarray(tree[kind][k][at[kind]]))
            at[kind] += 1
    assert at == {"conv": 4, "attn": 2, "dense": 2, "moe": 4}
    served = wts.params(TINY, ASSUMED, 9)
    assert served["moe"]["router"].dtype == served["moe"]["router_bias"].dtype \
        == jnp.float32 and served["moe"]["e_gate"].dtype == jnp.bfloat16
    assert float(jnp.std(served["moe"]["router_bias"])) > 0   # the bias runs
    assert float(jnp.std(served["conv"]["conv_w"].astype(jnp.float32))) > 0
    assert "lm_head" not in served                            # tied
    big = wts.layer(TINY, ASSUMED, 2**31 + 5, 1, jnp.float32)
    assert not np.array_equal(
        np.asarray(big["w_in"]),
        np.asarray(wts.layer(TINY, ASSUMED, 5, 1, jnp.float32)["w_in"]))


def _reference_logits(toks, seed, dtype=jnp.float32):
    kw = ref.layer_kwargs(TINY, ASSUMED)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            jnp.asarray(toks),
            [wts.layer(TINY, ASSUMED, seed, l, dtype)
             for l in range(TINY["num_hidden_layers"])],
            wts.ends(TINY, ASSUMED, seed, dtype), **kw))


def _served_margins(seed, control=None, n=4, prompt=12, new=8):
    """What the plane's `correct` compares, at the rehearsal's sizes and
    in its float32: an in-process engine's own answers, teacher-forced
    through the reference: (mean, largest) margin."""
    from ray_tpu.models import lfm2
    from ray_tpu.serve.llm_engine import LlamaEngine

    mcfg = plane.model_config(TINY, ASSUMED, jnp.float32)
    params = wts.params(TINY, ASSUMED, seed, jnp.float32)
    sound = lfm2.forward
    if control == "fp8":
        params = plane.fp8_weights(params)
    elif control == "conv_state_zero":
        plane.zero_conv_state_at_admission()
    e = {**CFG["engine"], **REH["engine"]}
    try:
        eng = LlamaEngine(mcfg, params, slots=e["slots"], max_len=e["max_len"],
                          chunk=e["chunk"], block_size=e["block_size"],
                          kv_blocks=e["kv_blocks"],
                          prefix_cache=e["prefix_cache"])
        try:
            rng = np.random.default_rng(seed)
            prompts = [rng.integers(1, TINY["vocab_size"], size=prompt).tolist()
                       for _ in range(n)]
            outs = [f.result(timeout=300)
                    for f in [eng.submit(p, new) for p in prompts]]
        finally:
            eng.shutdown()
    finally:
        lfm2.forward = sound
    vals = []
    for p, o in zip(prompts, outs):
        lg = _reference_logits(p + o[:-1], seed)[len(p) - 1:]
        vals.append(np.asarray(ref.margins(jnp.asarray(lg), jnp.asarray(o))))
    vals = np.concatenate(vals)
    return float(vals.mean()), float(vals.max())


def test_the_sound_program_is_correct_and_both_controls_are_not():
    """At the rehearsal's sizes, against the rehearsal's limits: the
    float32 program sits on the reference's argmax; every matmul weight
    through float8 does not; and a convolution state zeroed at
    admission does not either: the check SEES the state."""
    lim = (LIMITS["mean_margin_limit"], LIMITS["max_margin_limit"])
    mean, worst = _served_margins(7)
    assert mean <= lim[0] and worst <= lim[1], (mean, worst)
    for control in plane.CONTROLS:
        mean, worst = _served_margins(7, control)
        assert mean > 5 * lim[0], (control, mean, worst)


def test_the_fp8_control_rounds_every_matmul_weight_and_nothing_else():
    params = wts.params(TINY, ASSUMED, 3)
    ctl = plane.fp8_weights(wts.params(TINY, ASSUMED, 3))  # donates its own
    for stack, leaves in wts.LEAVES.items():
        for name in leaves:
            a, b = params[stack][name], ctl[stack][name]
            if name in plane.MATMUL_LEAVES:
                assert a.dtype == b.dtype and not jnp.array_equal(a, b), name
                a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
                rel = float(jnp.max(jnp.abs(a32 - b32)) / jnp.max(jnp.abs(a32)))
                assert 0 < rel < 0.07, name   # e4m3: 3 mantissa bits
            else:
                assert jnp.array_equal(a, b), name
    assert set(plane.MATMUL_LEAVES) == {
        n for ls in wts.LEAVES.values() for n in ls
        if not n.endswith("norm") and n not in ("router", "router_bias",
                                                "conv_w")}
    assert jnp.array_equal(ctl["tok_emb"], params["tok_emb"])
    with pytest.raises(ValueError, match="conv_state_zero"):
        plane.BenchHybridService.__init__(
            object.__new__(plane.BenchHybridService), {
                **CFG, "model": TINY, "assumed": ASSUMED,
                "engine": {**CFG["engine"], **REH["engine"]}},
            {}, 1, {"bench_dir": "/nonexistent", "rehearse": True,
                    "control": "int4"})


def test_roofline_counts_at_the_cells_shapes():
    m, a, e = CFG["model"], CFG["assumed"], CFG["engine"]
    peaks = manifest.peaks("TPU v5 lite")
    # one expert: 3 x 2048 x 1792 weights = 22.0 MB in bfloat16
    expert = 3 * 2048 * 1792 * 2
    assert expert == 22_020_096
    layers = m["num_hidden_layers"] - m["num_dense_layers"]
    pairs = 115 * m["num_experts_per_tok"]
    w = roofline_hybrid.moe_routed(pairs, 14 * 32, layers, m["hidden_size"],
                                   m["moe_intermediate_size"])
    rows = layers * pairs * (3 * 2048 + 1792) * 2
    assert w["bytes"] == 14 * 32 * expert + rows
    assert w["flops"] == 2 * 3 * 2048 * 1792 * pairs * layers
    least = roofline_hybrid.least_seconds(w, peaks)
    # 9.86 GB of experts at 819 GB/s
    assert least["bound"] == "memory" and 12.0e-3 < least["seconds"] < 12.3e-3
    # ONE attention layer: 72k live tokens x 8 heads x 64 x 2 (K, V) x 2 B
    p = roofline_hybrid.paged_decode(72_000, e["slots"],
                                     m["num_attention_heads"],
                                     m["num_key_value_heads"], a["head_dim"])
    assert p["bytes"] == 72_000 * 2048 + 2 * 128 * 32 * 64 * 2
    assert p["flops"] == 4 * 32 * 64 * 72_000
    least = roofline_hybrid.least_seconds(p, peaks)
    assert least["bound"] == "memory" and 1.8e-4 < least["seconds"] < 1.9e-4
    assert roofline_hybrid.cache_bytes(72_000, 115, 8192, 147456) == \
        72_000 * 8192 + 115 * 147456


def _ctx(scopes=None, ticks=(), kernels=None, engine=None):
    return {"plane": "serve", "config": CFG,
            "peaks": manifest.peaks("TPU v5 lite"),
            "replicas": [{
                "rid": "1", "tick_ring": list(ticks),
                "engine": engine or {},
                "trace": {"devices": 1, "scopes": scopes or {},
                          "kernels": kernels or {}}}]}


def test_the_new_readers_and_what_they_return_on_the_parent():
    tick = {"active": 128, "live_tokens": 72_000, "state_rows_live": 116,
            "row_steps_live": 920, "row_steps": 1024,
            "experts_touched": 448.0, "experts_total": 448,
            "expert_load_max": 31}
    kernels = {"paged_decode": {"seconds": 1.5, "calls": 10,
                                "op_seconds": 0.128, "op_calls": 10 * 8 * 4}}
    scopes = {"programs_s": 1.5, "program_calls": 10, "moe_routed": 1.1,
              "moe_router": 0.05, "short_conv": 0.06, "gqa_attn": 0.15,
              "dense_mlp": 0.02}
    engine = {"cache_bytes_per_token": 8192, "cache_bytes_per_slot": 147456}
    ctx = _ctx(scopes, [tick, {"active": 0}], kernels, engine)
    read = lambda n: manifest.layer_metric(n).read(ctx)  # noqa: E731
    assert read("hybrid_moe_device_share") == pytest.approx(100 * 1.15 / 1.5)
    assert read("short_conv_device_share") == pytest.approx(4.0)
    # 448 x 22.0 MB + rows = 9.97 GB = 12.2 ms; traced 13.75 ms a step
    assert 88 < read("hybrid_moe_routed_roofline") < 89.5
    # 72k tokens x 2 KB = 147.5 MB = 0.181 ms; traced 0.4 ms a call
    assert 45 < read("hybrid_paged_decode_roofline") < 46
    assert read("hybrid_cache_bytes_live") == 72_000 * 8192 + 116 * 147456
    # 920 / 8 = 115 live rows x 4 of 32 experts = 14.375 rows an expert
    assert read("hybrid_moe_expert_load_max_over_mean") == pytest.approx(
        31 / 14.375)
    assert read("engine_state_rows_live") == pytest.approx(116.0)
    assert read("decode_step_ms") == pytest.approx(1e3 * 1.5 / (8 * 10))
    # the parent: no scope, no counters, one cache kind
    parent = _ctx({"programs_s": 1.5, "program_calls": 10},
                  [{"active": 64, "live_tokens": 9000}], kernels,
                  {"cache_bytes_per_token": 65536, "cache_bytes_per_slot": 0})
    for name in NEW_METRICS:
        assert manifest.layer_metric(name).read(parent) is None, name
    # kanana's cell has `moe_*` scopes but no `short_conv`: not this reader's
    kanana = _ctx({**scopes, "short_conv": 0.0}, [tick], kernels, engine)
    assert manifest.layer_metric("hybrid_moe_device_share").read(kanana) is None


def test_kernel_predicates_find_the_three_kernels():
    pred = plane.kernel_predicates(CFG)
    attn = ("%closed_call.9 = bf16[128,32,512]{2,1,0} custom-call(s32[1] %a), "
            "custom_call_target=\"tpu_custom_call\"")
    append = ("%closed_call.3 = (bf16[4,10369,16,512]{3,2,1,0}, "
              "bf16[4,10369,16,512]{3,2,1,0}) custom-call(s32[1] %a), "
              "custom_call_target=\"tpu_custom_call\", "
              "output_to_operand_aliasing={{0}: (3, {})}")
    gmm = ("%gmm.1 = bf16[512,1792]{1,0} custom-call(s32[32] %g), "
           "custom_call_target=\"tpu_custom_call\"")
    fusion = "%fusion.3 = bf16[128,32,512]{2,1,0} fusion(bf16[128] %x)"
    assert pred["paged_decode"](attn) and not pred["paged_decode"](gmm)
    assert pred["paged_append"](append) and not pred["paged_append"](attn)
    assert pred["moe_grouped"](gmm) and not pred["moe_grouped"](attn)
    assert not any(p(fusion) for p in pred.values())


def test_the_cells_rehearsal_runs_to_correct_and_leaves_nothing_running():
    import test_bench_guard as guard

    proc, mark = guard.start(CELL, "--trace", "1")
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 3, err[-3000:]
    assert "rehearsal passed" in err
    said = next(l for l in err.splitlines() if "rehearsal: " in l)
    said = json.loads(said.split("rehearsal: ", 1)[1])
    assert said["correct"] is True
    assert "hybrid_cache_bytes_live" in said["metrics"]
    assert "engine_state_rows_live" in said["metrics"]
    assert '"correct"' not in out.strip().splitlines()[-1]
    assert '"metrics"' not in out
    guard.assert_clean(mark)
