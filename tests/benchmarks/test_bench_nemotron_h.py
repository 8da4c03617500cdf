"""The recurrent-state configuration (`nemotron3-super-l11-ep4`) and its
cell: the configuration's file against the catalog, the seeded weights,
the system against the plain reference at the rehearsal's widths, the
controls, the rooflines' and the needed operations' counts by hand,
every new reader on a recorded context and on another cell's, the
manifest's entries, and the cell's rehearsal."""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_bench_manifest as held
from benchmarks import manifest, roofline_nemotron_h as rl
from benchmarks import weights_nemotron_h as wts
from benchmarks.planes import serve_recurrent as plane
from benchmarks.reference import nemotron_h as ref

NAME, CELL, MIX = ("nemotron3-super-l11-ep4", "nemotron3s_mixed_closed_8k",
                   "agent_closed_8k_a512")
CFG = manifest.config(NAME)
TINY = {**CFG["model"], **CFG["rehearsal"]["model"]}
TDEP = {**CFG["deployment"], **CFG["rehearsal"]["deployment"]}
STD = dict(std=0.2, out_std=0.2 / 88 ** 0.5)
NEW_METRICS = ("ssm_scan_prefill_roofline", "ssm_step_decode_roofline",
               "latent_moe_routed_roofline", "ssm_device_share",
               "latent_moe_device_share", "latent_moe_held_pairs_per_token",
               "latent_moe_expert_load_max_over_mean", "ssm_state_bytes_live",
               "ssm_resumed_chunks_per_request")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _reference_logits(toks, seed, dtype=jnp.float32):
    ends = wts.ends(TINY, seed, dtype, std=STD["std"])
    with jax.default_matmul_precision("highest"):
        x = ref.embed(jnp.asarray(toks), ends["tok_emb"])
        for l in range(TINY["num_hidden_layers"]):
            x = ref.layer(x, wts.layer(TINY, TDEP, seed, l, dtype, **STD),
                          qblock=8,
                          **ref.layer_kwargs(TINY, l, TDEP["expert_offset"]))
        return np.asarray(ref.head(x, ends["final_norm"], ends["lm_head"],
                                   TINY["layer_norm_epsilon"]))


def _system_logits(toks, seed, dtype, params=None):
    from ray_tpu.models import nemotron_h

    mcfg = plane.model_config(TINY, TDEP, dtype)
    if params is None:
        params = wts.params(TINY, TDEP, seed, dtype, **STD)
    return np.asarray(nemotron_h.forward(
        mcfg, params, jnp.asarray(toks)[None])[0][0])


def _toks(seed=4, T=48):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], size=T)


# ----------------------------------------------------------------------
def test_the_reference_imports_nothing_from_the_program_and_scans_no_chunk():
    src = inspect.getsource(ref)
    assert "ray_tpu" not in src.replace("nothing from `ray_tpu`", "")
    imports = "".join(l for l in src.splitlines() if "import" in l)
    assert "ssd" not in imports and "models" not in imports
    # one token at a time: a `lax.scan` over the tokens, no cumulative sum
    assert "jax.lax.scan(token" in src and "cumsum" not in src


def test_the_configuration_copies_the_catalog_and_lists_its_cuts():
    m = CFG["model"]
    assert all(CFG[k] == v for k, v in m.items())       # the two copies
    cut = {"num_hidden_layers": 11, "n_routed_experts": 128,
           "vocab_size": 32768, "hybrid_override_pattern": "MEMEMEM*EME"}
    assert {k: m[k] for k in cut} == cut
    assert sorted(CFG["reduced"]) == sorted(cut)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(l) for l in f if l.startswith(
                '{"name": "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"'))
        assert CFG["source"] == row["source_url"]
        assert {k: v for k, v in m.items() if k not in cut} == \
            {k: v for k, v in row["config"].items() if k not in cut}
        pattern = row["config"]["hybrid_override_pattern"]
        assert pattern[:11] == m["hybrid_override_pattern"]
        assert (len(pattern), pattern.count("M"), pattern.count("E"),
                pattern.count("*")) == (88, 40, 40, 8)
        pub = CFG["published"]
        assert all(row["config"][k] == pub[k] for k in (
            "num_hidden_layers", "n_routed_experts", "vocab_size",
            "hybrid_override_pattern"))
    # no width is cut
    for k in ("hidden_size", "head_dim", "mamba_head_dim", "mamba_num_heads",
              "ssm_state_size", "n_groups", "moe_latent_size",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "num_experts_per_tok", "expand", "conv_kernel"):
        assert k not in CFG["reduced"]
    for k in ("initializer_range", "rope", "latent_path", "gated_norm", "dt",
              "mamba_init", "rescale_prenorm_residual", "route_eps",
              "e_score_correction_bias", "state_dtype"):
        assert k in CFG["assumed"], k
    assert "mtp" in CFG["left_out"]
    dep = CFG["deployment"]
    assert (dep["chips_per_layer"], dep["router_experts"],
            dep["experts_per_chip"], dep["vocab_shards"],
            dep["expert_offset"]) == (4, 512, 128, 4, 0)
    assert dep["experts_per_chip"] * dep["chips_per_layer"] == \
        CFG["published"]["n_routed_experts"]
    assert m["vocab_size"] * dep["vocab_shards"] == \
        CFG["published"]["vocab_size"]
    # the floors: a whole period in the published 5 : 5 : 1 and >= 4
    # layers, >= 8 experts, >= 1/8 of the vocabulary
    p = m["hybrid_override_pattern"]
    assert (p.count("M"), p.count("E"), p.count("*")) == (5, 5, 1)
    assert m["n_routed_experts"] >= 8 and dep["vocab_shards"] <= 8


def test_the_cut_weighs_what_the_file_says():
    """9.30 GB of bfloat16 weights, counted from the shapes; the states
    and the blocks beside them."""
    m, dep, e = CFG["model"], CFG["deployment"], CFG["engine"]
    count = lambda shp: sum(int(np.prod(s)) for s in shp.values())  # noqa: E731
    layers = [count(wts.shapes(m, dep, wts.kind_of(m, l)))
              for l in range(m["num_hidden_layers"])]
    assert [round(n / 1e6, 2) for n in layers[:2] + [layers[7]]] == [
        109.64, 759.17, 35.66]
    total = sum(layers) + 2 * m["vocab_size"] * m["hidden_size"] \
        + m["hidden_size"]
    assert 4.64e9 < total < 4.66e9 and 9.29e9 < 2 * total < 9.31e9
    # the uncut model from the same shapes: 120.67B
    full = {**m, "n_routed_experts": 512}
    per = {k: count(wts.shapes(full, dep, k)) for k in "M*E"}
    whole = 40 * per["M"] + 8 * per["*"] + 40 * per["E"] \
        + 2 * 131072 * m["hidden_size"]
    assert 120.6e9 < whole < 120.75e9
    per_token = 1 * 2 * 2 * 128 * 2
    per_slot = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert (per_token, per_slot) == (1024, 21278720)
    pool = (e["kv_blocks"] + 1) * e["block_size"] * per_token
    assert 0.67e9 < pool < 0.68e9
    assert 2.72e9 < e["slots"] * per_slot < 2.73e9
    # ~80% of 16 GB before any activation
    assert 0.78 < (2 * total + pool + e["slots"] * per_slot) / 16e9 < 0.82
    mix = manifest.traffic(MIX)
    longest = max(mix["prompt_len"]["choices"]) + mix["output_len"]["fixed"]
    assert e["max_len"] >= longest + e["block_size"]
    for p in mix["prompt_len"]["choices"]:
        assert p % e["block_size"] == 0
        assert p <= e["prefill_chunk"] or p % e["prefill_chunk"] == 0


def test_layer_from_seed_equals_the_tree():
    params = wts.params(TINY, TDEP, 9, jnp.float32, **STD)
    for l in (0, 1, 7):
        again = wts.layer(TINY, TDEP, 9, l, jnp.float32, **STD)
        assert set(again) == set(params["layers"][l])
        for k, v in again.items():
            assert np.array_equal(np.asarray(v),
                                  np.asarray(params["layers"][l][k])), (l, k)
    mamba, experts = params["layers"][0], params["layers"][1]
    assert experts["router"].shape[-1] == TDEP["router_experts"]
    assert experts["e_up"].shape[0] == TINY["n_routed_experts"]
    assert "e_gate" not in experts and "wqkv" in params["layers"][7]
    # Mamba-2's usual initialisation: steps in [1e-3, 1e-1], A in [1, 16]
    step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    A = np.exp(np.asarray(mamba["A_log"]))
    assert 1.0 <= A.min() and A.max() <= 16.0
    assert np.all(np.asarray(mamba["D"]) == 1.0)
    assert np.abs(np.asarray(mamba["conv_w"])).max() <= 0.5
    # out_proj rescaled by the published depth
    assert float(jnp.std(mamba["out_proj"])) < 0.2 * float(
        jnp.std(mamba["in_proj"]))


def test_the_system_equals_the_reference_float32():
    toks = _toks()
    want = _reference_logits(toks, 4)
    got = _system_logits(toks, 4, jnp.float32)
    assert np.abs(got - want).max() < 3e-4
    assert want.std() > 0.3


def test_the_fp8_control_is_another_result():
    toks = _toks()
    want = _reference_logits(toks, 4)
    params = plane.fp8_weights(wts.params(TINY, TDEP, 4, jnp.float32, **STD))
    for l, k in ((1, "router"), (0, "conv_w"), (0, "A_log")):
        assert np.array_equal(
            np.asarray(params["layers"][l][k]),
            np.asarray(wts.layer(TINY, TDEP, 4, l, jnp.float32, **STD)[k]))
    got = _system_logits(toks, 4, jnp.float32, params)
    assert np.abs(got - want)[-8:].max() > 1e-2


@pytest.mark.parametrize("control", ["chunk_state_zero", "ssm_state_bf16",
                                     "prefill_state_zero"])
def test_a_state_control_patches_the_scans(control, monkeypatch):
    """The three controls of the STATE: a later chunk from a zeroed
    state is another result at 100 x the tolerance, whether the chunk
    before left it zero or this one took it for zero; a state rounded
    to bfloat16 wherever it is written differs by bfloat16's
    rounding."""
    from ray_tpu.models import nemotron_h
    from ray_tpu.ops import ssd

    mcfg = plane.model_config(TINY, TDEP, jnp.float32)
    params = wts.params(TINY, TDEP, 4, jnp.float32, **STD)
    toks = jnp.asarray(_toks(T=32))

    def two_chunks():
        cache = (jnp.zeros((1, 9, 8, 32)), jnp.zeros((1, 9, 8, 32)),
                 jnp.zeros((5, 2, 16, 8, 16)), jnp.zeros((5, 2, 3 * 192)))
        table = jnp.arange(1, 9, dtype=jnp.int32)
        for lo in (0, 16):
            logits, cache = nemotron_h.forward_chunk(
                mcfg, params, toks[lo:lo + 16], jnp.int32(lo), jnp.int32(16),
                cache, table, jnp.int32(1))
        return np.asarray(logits), np.asarray(cache[2][:, 1])

    sound, state = two_chunks()
    for name in ("ssd_scan", "conv_scan", "ssd_step"):
        monkeypatch.setattr(ssd, name, getattr(ssd, name))  # put back after
    {"chunk_state_zero": plane.start_chunks_from_zero,
     "ssm_state_bf16": plane.hold_state_in_bf16,
     "prefill_state_zero": plane.leave_no_state}[control]()
    got, held = two_chunks()
    if control == "prefill_state_zero":
        assert not held.any() and np.abs(got - sound).max() > 3e-2
    elif control == "chunk_state_zero":
        assert np.abs(got - sound).max() > 3e-2 and held.any()
    else:
        assert 0 < np.abs(held - state).max() < 2e-2 * np.abs(state).max()
        assert np.array_equal(held, held.astype(jnp.bfloat16).astype(
            np.float32))


def test_the_state_rows_tell_a_chunk_that_started_from_nothing(monkeypatch):
    """`verdict`'s state rows at the rehearsal's widths: the leaves a
    slot holds 2 tokens into a prompt's second chunk against the
    reference's state after as many tokens (one token at a time from
    zero, `keep`), a head at a time as `_state_errors` reads them: float32
    rounding apart for the sound program, and of the state's own size
    when the second chunk started from nothing, the recurrent state and
    the convolution's inputs alike (with a tail under `conv_kernel - 1`
    tokens the leaf still holds a row of the chunk before)."""
    from ray_tpu.models import nemotron_h
    from ray_tpu.ops import ssd

    mcfg = plane.model_config(TINY, TDEP, jnp.float32)
    params = wts.params(TINY, TDEP, 4, jnp.float32, **STD)
    toks = _toks(T=18)

    def slot_after_two_chunks():
        cache = (jnp.zeros((1, 9, 8, 32)), jnp.zeros((1, 9, 8, 32)),
                 jnp.zeros((5, 2, 16, 8, 16)), jnp.zeros((5, 2, 3 * 192)))
        table = jnp.arange(1, 9, dtype=jnp.int32)
        for lo, n in ((0, 16), (16, 2)):
            _, cache = nemotron_h.forward_chunk(
                mcfg, params, jnp.asarray(np.pad(toks[lo:lo + n],
                                                 (0, 16 - n))),
                jnp.int32(lo), jnp.int32(n), cache, table, jnp.int32(1))
        return {"tokens": list(toks), "ssm": np.asarray(cache[2][:, 1]),
                "conv": np.asarray(cache[3][:, 1]), "ssm_rel_err": [],
                "conv_rel_err": []}

    def errors(probe):
        with jax.default_matmul_precision("highest"):
            x = ref.embed(jnp.asarray(np.pad(toks, (0, 6))),
                          wts.ends(TINY, 4, jnp.float32,
                                   std=STD["std"])["tok_emb"])
            for l in range(TINY["num_hidden_layers"]):
                x, held = ref.layer(
                    x, wts.layer(TINY, TDEP, 4, l, jnp.float32, **STD),
                    qblock=8, keep=jnp.int32(18),
                    **ref.layer_kwargs(TINY, l, TDEP["expert_offset"]))
                plane._state_errors([probe], None if held is None else {
                    k: v[None] for k, v in held.items()})
        assert len(probe["ssm_rel_err"]) == 5       # the Mamba layers
        return max(probe["ssm_rel_err"]), max(probe["conv_rel_err"])

    ssm, conv = errors(slot_after_two_chunks())
    assert ssm < 1e-4 and conv < 1e-5
    for name in ("ssd_scan", "conv_scan"):
        monkeypatch.setattr(ssd, name, getattr(ssd, name))  # put back after
    plane.start_chunks_from_zero()
    ssm, conv = errors(slot_after_two_chunks())
    assert ssm > 0.3 and conv > 0.3


def test_the_verdict_holds_the_slots_states():
    lim = {**CFG["reference"], "state_probe": {
        "ssm_rel_err_limit": 0.1, "conv_rel_err_limit": 0.05}}
    check = {"sampled": 8, "tokens": 4096, "mean_margin": 0.0016,
             "max_margin": 0.2}

    def rows(state):
        ctx = {"traffic": {"kind": "closed_loop"},
               "client": {"cut_at_end": 0},
               "replicas": [{"check": {**check, **state}}]}
        v = plane.verdict(ctx, {"reference": lim})
        return v["correct"], {r[0]: r[1] for r in v["rows"]}

    sound = {"state": {"probes": [{}], "ssm_rel_err": 0.01,
                       "conv_rel_err": 0.004}}
    ok, got = rows(sound)
    assert ok and got["slot_ssm_state_rel_err_from_reference"] == 0.01
    for leaf in ("ssm", "conv"):
        off = {"state": {**sound["state"], f"{leaf}_rel_err": 0.6}}
        assert not rows(off)[0]
    # no probe read (no long prompt was served): no verdict
    assert not rows({})[0]
    assert not rows({"state": {"probes": [], "ssm_rel_err": 0.0,
                               "conv_rel_err": 0.0}})[0]


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="controls are"):
        plane.BenchRecurrentService(
            {**CFG, "model": TINY, "deployment": TDEP}, {}, 1,
            {"bench_dir": "/nonexistent", "rehearse": True,
             "control": "int8"})


def test_the_traffic_is_the_issues():
    mix = manifest.traffic(MIX)
    assert (mix["kind"], mix["clients"]) == ("closed_loop", 192)
    other = manifest.traffic("mixed_closed_8k_a512")
    assert mix["mix_seed"] != other["mix_seed"]
    # the same queue under another architecture
    for k in ("prompt_len", "output_len", "first_output_step", "clients",
              "requests_per_client", "drain_s", "trace_s"):
        assert mix[k] == other[k], k
    assert mix["prompt_len"] == {"choices": [1024, 4096, 8192],
                                 "weights": [2, 1, 1]}
    assert mix["output_len"] == {"fixed": 512}
    assert (mix["first_output_step"], mix["requests_per_client"],
            mix["drain_s"], mix["trace_s"]) == (16, 24, 30.0, 3.0)
    assert "shared_prefix" not in mix
    assert CFG["engine"]["slots"] == 128


# -- the counts, by hand at one shape -----------------------------------
def test_roofline_counts_at_the_cells_shapes():
    peaks = manifest.peaks("TPU v5 lite")
    elems = 128 * 64 * 128
    s = rl.ssm_scan(2048, 1, 128, 64, 8, 128)
    assert s["flops"] == 5 * elems * 2048
    assert s["bytes"] == 2048 * ((2 * 8192 + 2 * 1024) * 2 + 512) + 8 * elems
    # 10.7 GFLOP: 0.054 ms; 85 MB: 0.10 ms
    assert rl.least_seconds(s, peaks)["bound"] == "memory"
    assert 0.09e-3 < rl.least_seconds(s, peaks)["seconds"] < 0.11e-3
    d = rl.ssm_step(115, 4096, 128, 64, 8, 128, 4)
    proj = 4096 * 18560 + 8192 * 4096
    assert d["bytes"] == 115 * (8 * elems + 2 * 3 * 10240 * 2) + 2 * proj
    # 979 MB of states + 219 MB of projections a layer: 1.46 ms
    assert rl.least_seconds(d, peaks)["bound"] == "memory"
    assert 1.44e-3 < rl.least_seconds(d, peaks)["seconds"] < 1.48e-3
    e = rl.latent_moe_routed(115, 22, 128, 512, 5 * 127.0, 5, 1024, 2688)
    expert = 2 * 1024 * 2688 * 2
    assert expert == 11_010_048
    assert 0.99 < (635 * expert) / e["bytes"] <= 1.0
    assert e["flops"] == 5 * 115 * 22 / 4 * 4 * 1024 * 2688
    # 7.0 GB of held experts: 8.5 ms
    assert 8.4e-3 < rl.least_seconds(e, peaks)["seconds"] < 8.7e-3


def test_the_needed_operations_count_the_weights_a_position_passes():
    mod = manifest.needed_flops(CFG["plane"])
    w = mod.matmul_weights(CFG)
    m = CFG["model"]
    mamba = 4096 * 18560 + 8192 * 4096
    attn = 4096 * 36 * 128 + 4096 * 4096
    experts = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + 22 * 128 / 512 * 2 * 1024 * 2688)
    assert w["layers"] == pytest.approx(5 * mamba + attn + 5 * experts)
    assert w["head"] == 32768 * 4096
    assert 1.0e9 < w["layers"] < 1.02e9           # ~2.0 GFLOP a position
    # against the shapes the weights are built in
    tree = jax.eval_shape(lambda: wts.params(m, CFG["deployment"], 0))
    leaves = {}
    for layer in tree["layers"]:
        for k, v in layer.items():
            if len(v.shape) >= 2 and k != "conv_w":
                n = int(np.prod(v.shape))
                if k in ("e_up", "e_down"):
                    n = n // 128 * 22 * 128 / 512
                leaves[k] = leaves.get(k, 0) + n
    assert sum(leaves.values()) == pytest.approx(w["layers"])
    mix = manifest.traffic(MIX)
    short, long = (mod.request_flops(CFG, mix, p, 512, {})
                   for p in (1024, 8192))
    assert long > short > 2 * w["layers"] * 1535
    # a position's recurrence and convolution: 5 layers' worth
    assert mod.scan_flops(m) == 5 * (5 * 8192 * 128 + 2 * 4 * 10240)


# -- the readers ---------------------------------------------------------
def _ctx(scopes=None, ticks=(), prefill=None, kernels=None, launches=None,
         modules=None):
    engine = {"request_ring": [], "cache_bytes_per_token": 1024,
              "cache_bytes_per_slot": 21278720}
    if launches:
        engine["launch_account"] = launches
    trace = {"devices": 1, "scopes": scopes or {}}
    if prefill:
        trace["prefill_scopes"] = prefill
    if kernels:
        trace["kernels"] = kernels
    if modules:
        trace["module_calls"] = modules
    return {"plane": "serve", "config": CFG, "setup_s": 10.5, "seconds": 4.0,
            "peaks": manifest.peaks("TPU v5 lite"),
            "client": {"per_replica": {"1": 0}},
            "replicas": [{"rid": "1", "tick_ring": list(ticks),
                          "engine": engine, "trace": trace}]}


FIELDS = ("program", "traced", "launches", "rows", "tokens", "padded_tokens",
          "attended_pairs", "rows_live", "live_tokens", "launch_us",
          "blocked")


def test_every_new_reader_reads_a_recorded_context(monkeypatch):
    monkeypatch.setenv("RT_BENCH_T0", "1000.0")
    tick = {"t_wall": 1011.2, "active": 120, "admitted": 2,
            "live_tokens": 490_000, "state_rows_live": 115, "row_steps": 1024,
            "row_steps_live": 920, "experts_touched": 635.0,
            "experts_total": 640, "expert_load_max": 17, "experts_held": 128,
            "held_pairs": 920 * 5 * 5.5, "ssm_bytes_live": 115 * 21278720,
            "full_cache_tokens_live": 490_000, "state_chunks_resumed": 2}
    # 10 programs of 8 steps: 80 steps; 5 Mamba and 5 expert layers
    scopes = {"programs_s": 2.0, "program_calls": 10, "ssm_step": 0.48,
              "ssm_conv": 0.04, "ssm_proj": 0.28, "latent_moe_routed": 0.8,
              "latent_moe_proj": 0.04, "moe_router": 0.04,
              "moe_shared": 0.08, "full_attn": 0.1}
    prefill = {"programs_s": 0.9, "program_calls": 12, "ssm_scan": 0.12,
               "latent_moe_routed": 0.3}
    launches = {"fields": FIELDS, "rows": [
        ["prefill_chunk_n2048", 1, 9, 9, 9 * 2048, 9 * 2048, 0, 0, 0, 900, 0],
        ["prefill_packed_n2048", 1, 3, 6, 6 * 1024, 3 * 2048, 0, 0, 0, 300,
         0],
        ["prefill_chunk_n2048", 0, 50, 50, 50 * 2048, 50 * 2048, 0, 0, 0, 5000,
         0]]}
    modules = {"jit_prefill_chunk_n2048(123)": 9,
               "jit_prefill_packed_n2048(456)": 3,
               "jit_decode_chunk_w1024(789)": 10}
    kernels = {"paged_decode": {"seconds": 2.0, "calls": 10,
                                "op_seconds": 0.08, "op_calls": 80}}
    # set-up's ticks (before 1010.5) and the drain's are not the window's
    warm = {**tick, "t_wall": 1003.0, "held_pairs": 1, "ssm_bytes_live": 9}
    late = {**tick, "t_wall": 1014.6, "held_pairs": 1, "ssm_bytes_live": 9}
    ctx = _ctx(scopes, [warm, tick, {**tick, "t_wall": 1012.9},
                        {"t_wall": 1013.0, "active": 0, "live_tokens": 0},
                        late], prefill, kernels, launches, modules)
    read = lambda n: manifest.layer_metric(n).read(ctx)  # noqa: E731
    # 12 programs x 2,048 tokens x 5 layers: 5 x 12 x 0.104 ms = 6.2 ms
    # against 0.12 s
    assert 5.0 < read("ssm_scan_prefill_roofline") < 5.5
    # (0.48 + 0.04 + 0.28) / 80 = 10 ms against 5 x 1.46 ms
    assert 72 < read("ssm_step_decode_roofline") < 74
    # 0.8 s / 80 = 10 ms against 8.5 ms
    assert 84 < read("latent_moe_routed_roofline") < 87
    assert read("ssm_device_share") == pytest.approx(40.0)
    assert read("latent_moe_device_share") == pytest.approx(48.0)
    assert read("latent_moe_held_pairs_per_token") == pytest.approx(5.5)
    # 17 rows at the fullest against 920 x 5 x 5.5 / (8 x 5 x 128)
    assert read("latent_moe_expert_load_max_over_mean") == pytest.approx(
        17 / (920 * 27.5 / 5120))
    assert read("ssm_state_bytes_live") == pytest.approx(115 * 21278720)
    assert read("ssm_resumed_chunks_per_request") == pytest.approx(1.0)
    for name in NEW_METRICS:
        v = read(name)
        assert v is not None and (0 < v <= 100 or "roofline" not in name), name
    # no join of the launch account to the trace: the programs' own rows
    del ctx["replicas"][0]["engine"]["launch_account"]
    assert 5.0 < read("ssm_scan_prefill_roofline") < 5.5
    assert manifest.layer_metric("decode_step_ms").read(ctx) == \
        pytest.approx(1e3 * 2.0 / 80)


def test_a_program_the_traces_edge_cut_counts_the_steps_it_holds():
    """Two decode programs in a trace, the second cut after two steps
    (the chip, PR 63: 0.2708 s where two whole programs take 0.42): the
    steps are the paged kernel's calls, 10, not 2 x 8, so a roofline
    share cannot read over 100 for a cut program's missing time."""
    tick = {"t_wall": 1011.2, "active": 120, "live_tokens": 490_000,
            "state_rows_live": 115, "row_steps": 1024, "row_steps_live": 920,
            "experts_touched": 635.0, "experts_total": 640,
            "expert_load_max": 17, "experts_held": 128,
            "held_pairs": 920 * 27.5, "ssm_bytes_live": 115 * 21278720}
    scopes = {"programs_s": 0.2708, "program_calls": 2, "ssm_step": 0.1207,
              "ssm_conv": 0.0027, "ssm_proj": 0.015,
              "latent_moe_routed": 0.0949}
    kernels = {"paged_decode": {"seconds": 0.2708, "calls": 2,
                                "op_seconds": 0.016, "op_calls": 10}}
    os.environ["RT_BENCH_T0"] = "1000.0"
    try:
        ctx = _ctx(scopes, [tick], kernels=kernels)
        moe = manifest.layer_metric("latent_moe_routed_roofline").read(ctx)
        ssm = manifest.layer_metric("ssm_step_decode_roofline").read(ctx)
        assert 85 < moe < 95 and 50 < ssm < 56
        # without the kernel's count: the programs' calls x the chunk
        whole = _ctx(scopes, [tick])
        assert manifest.layer_metric(
            "latent_moe_routed_roofline").read(whole) > 1.5 * moe
    finally:
        del os.environ["RT_BENCH_T0"]


def test_the_new_readers_read_nothing_on_another_cell_or_the_parent():
    """A cell of another model, and this cell on a program without the
    scopes or the counters: None, never an error."""
    mimo = {**_ctx({"programs_s": 1.2, "program_calls": 10,
                    "moe_routed": 0.8, "full_attn": 0.1},
                   [{"active": 64, "admitted": 1, "live_tokens": 64 * 700,
                     "state_rows_live": 60, "experts_touched": 90.0,
                     "experts_total": 96, "expert_load_max": 9,
                     "experts_held": 16, "row_steps": 1024,
                     "row_steps_live": 500, "window_rows_live": 7000,
                     "ring_bytes_live": 60 * 3276800,
                     "full_cache_tokens_live": 64 * 700}],
                   {"program_calls": 4, "moe_routed": 0.1}),
            "config": manifest.config("mimo-v2.5-l7-ep16")}
    parent = _ctx({}, [{"active": 120, "admitted": 1, "live_tokens": 1000,
                        "state_rows_live": 100, "row_steps_live": 800}])
    os.environ["RT_BENCH_T0"] = "1000.0"
    try:
        for name in NEW_METRICS:
            for ctx in (mimo, parent, {"plane": "train"}):
                assert manifest.layer_metric(name).read(ctx) is None, name
    finally:
        del os.environ["RT_BENCH_T0"]


def test_the_kernel_is_found_by_what_it_returns():
    pred = plane.kernel_predicates(CFG)
    attn = ('%closed_call.3 = bf16[128,32,256]{2,1,0} custom-call(...), '
            'custom_call_target="tpu_custom_call"')
    other = attn.replace("bf16[128,32,256]", "bf16[2,16,2048,128]")
    assert pred["paged_decode"](attn) and not pred["paged_decode"](other)
    assert pred["paged_append"](
        'x = (bf16[1,40961,16,256]) custom-call(...), custom_call_target='
        '"tpu_custom_call", output_to_operand_aliasing={...}')


# -- the manifest ---------------------------------------------------------
# the closed cells that stood in the shared lists before this one
BEFORE = ["mistral7b_batch_closed", "kanana2_batch_closed_1k",
          "brumby14b_batch_closed_1k", "lfm2_batch_closed_512",
          "dots3_docqa_closed_16k", "mimo25_mixed_closed_8k",
          "sdar30b_blockgen_closed_512"]


# the lists every closed cell shares: PR 54's sixteen with `serve_mfu`
# (which lists no cell) and the launch stamp's two
SHARED = held.CLOSED_SHARED | {"engine_launch_blocked_share",
                               "engine_tick_host_own_ms"}


def check_the_manifest_finds_every_new_file():
    """What PR 63 added is held BY NAME: where in its list an entry
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
    assert CFG["plane"] == "serve_recurrent"
    assert callable(manifest.needed_flops(CFG["plane"]).request_flops)
    e2e = [e["name"] for e in manifest.metrics_for(CELL, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    per_layer = manifest.metrics_for(CELL, "per_layer")
    names = [p["name"] for p in per_layer]
    # the new entries are all listed, in the issue's order among
    # themselves, wherever they stand
    listed = [p["name"] for p in man["per_layer"] if p["name"] in NEW_METRICS]
    assert tuple(listed) == NEW_METRICS
    assert [n for n in names if n in NEW_METRICS] == list(NEW_METRICS)
    # the shared readers of a closed cell (`serve_mfu` among them, the
    # launch stamp's two); the two accepted readers whose counts fit
    # this cell as they stand (the per-slot states a chunk's first step
    # moves; the paged decode kernel on `num_key_value_heads` x
    # `head_dim` = 256 lanes a token, K and V read once: the attention
    # layer's folded row); and none that counts another model's widths
    assert SHARED | {"engine_state_rows_live",
                     "paged_decode_roofline"} <= set(names)
    assert not {"mla_decode_roofline", "moe_routed_roofline",
                "hybrid_paged_decode_roofline",
                "gqa_full_decode_roofline", "ep16_moe_routed_roofline",
                "retention_decode_roofline"} & set(names)
    for p in per_layer:
        mod = manifest.layer_metric(p["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            p["layer"], p["unit"], p["source"], p["moves"]), p["name"]
        assert p["moves"] == "serve_tokens_per_s"
    for p in man["per_layer"] + man["end_to_end"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"][0] == CELL and CELL in p["workloads"]
        elif "workloads" in p and (p["name"] in SHARED
                                   or p["name"] == "serve_tokens_per_s"):
            # appended: every cell that stood there still stands before it
            at = p["workloads"].index(CELL)
            assert p["workloads"][:at] == BEFORE, p["name"]
    for name, before in (
            ("engine_state_rows_live", ["brumby14b_batch_closed_1k",
                                        "lfm2_batch_closed_512"]),
            ("paged_decode_roofline", ["mistral7b_batch_closed"])):
        p = next(p for p in man["per_layer"] if p["name"] == name)
        assert p["workloads"][:p["workloads"].index(CELL)] == before


def test_the_manifest_finds_every_new_file():
    check_the_manifest_finds_every_new_file()


def test_the_grown_manifest_passes_the_checks_that_hold_earlier_entries():
    """`test_a_list_can_grow`'s checks, on the manifest as this PR
    leaves it."""
    for module, check in held.manifest_checks().items():
        check()
    held.check_every_cell_reports_enough_and_uses_a_known_config(
        manifest.manifest())


def test_the_parent_fails_at_once_on_the_missing_model(monkeypatch):
    monkeypatch.setattr(plane, "NEED", (("models", "no_such_model.py"),))
    with pytest.raises(RuntimeError, match="cannot run"):
        plane.run({"name": CELL}, CFG, {}, None, 0.0)


# The closed mix's row stands in `closed_sizes/agent_closed_8k_a512.json`:
# the set check of `test_bench_manifest.py` holds it against the mix's
# file and this configuration's slots, and the three checks that
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
