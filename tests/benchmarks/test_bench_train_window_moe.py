"""The trained sparse configuration (`trinity-mini-l5-train`) and its
cell: the configuration's file against the catalog, the seeded weights,
the plain reference against a second computation of one layer, the
FLOP and byte counts by hand, the ten `afmoe_*` readers on a recorded
context and on contexts that are not theirs, the plane's scope matcher
and kernel names, the manifest's entries BY NAME, and the cell's
rehearsal."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest
from benchmarks import roofline_train_window_moe as rl
from benchmarks import weights_afmoe as wts
from benchmarks.planes import train_window_moe as plane
from benchmarks.reference import afmoe as ref

NAME, CELL, MIX = ("trinity-mini-l5-train", "trinity_mini_train_8k",
                   "train_stream_8k")
CFG = manifest.config(NAME)
NEW_METRICS = (
    "afmoe_train_mfu", "afmoe_flash_fwd_roofline", "afmoe_flash_bwd_roofline",
    "afmoe_gmm_roofline", "afmoe_tgmm_roofline", "afmoe_moe_device_share",
    "afmoe_attn_device_share", "afmoe_optimizer_device_share",
    "afmoe_expert_load_max_over_mean", "afmoe_held_pairs_per_token")
GPT2_ONLY = ("train_mfu", "flash_fwd_roofline", "flash_bwd_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16,
       "vocab_size": 25024,
       "layer_types": ["sliding_attention"] * 4 + ["full_attention"]}


# -- the configuration ----------------------------------------------------
def test_the_configuration_copies_the_catalog_and_lists_its_cuts():
    m = CFG["model"]
    assert all(CFG[k] == v for k, v in m.items())       # the two copies
    assert {k: m[k] for k in CUT} == CUT
    assert sorted(CFG["reduced"]) == sorted(CUT)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(l) for l in f
                       if l.startswith('{"name": "Trinity-Mini"'))
        assert CFG["source"] == row["source_url"]
        assert {k: v for k, v in m.items() if k not in CUT} == \
            {k: v for k, v in row["config"].items() if k not in CUT}
        # the first layer and one whole period of the published pattern
        assert m["layer_types"] == (row["config"]["layer_types"][:1]
                                    + row["config"]["layer_types"][4:8])
        pub = CFG["published"]
        assert all(row["config"][k] == pub[k] for k in (
            "num_hidden_layers", "num_dense_layers", "num_experts",
            "vocab_size"))
    d = CFG["deployment"]
    assert d["router_experts"] == CFG["published"]["num_experts"] == 128
    assert d["experts_per_chip"] == m["num_experts"]
    assert m["vocab_size"] * d["vocab_shards"] == 200192
    # guide section 4's floors: a whole period behind the dense layer,
    # >= 8 experts, >= 1/8 of the vocabulary; no width among the cuts
    assert m["num_hidden_layers"] - m["num_dense_layers"] == 4
    assert m["layer_types"][1:] == ["sliding_attention"] * 3 + [
        "full_attention"]
    for k in ("hidden_size", "head_dim", "intermediate_size",
              "moe_intermediate_size", "num_experts_per_tok",
              "num_attention_heads", "num_key_value_heads"):
        assert k not in CFG["reduced"]
    assert CFG["plane"] == "train_window_moe"
    assert set(CFG["rehearsal"]) >= {"model", "deployment", "trainer",
                                     "reference"}


def test_the_traffic_is_one_sequence_of_8k_from_the_slice():
    mix = manifest.traffic(MIX)
    assert (mix["kind"], mix["seq"], mix["zipf_a"]) == (
        "train_stream", 8192, 1.1)
    # the issue's fallback, said in the file: 2 x 8,192 does not fit
    assert mix["batch"] == 1 and "17.4 GB" in mix["why"]
    assert (mix["ahead_steps"], mix["trace_s"]) == (12, 3.0)
    m, held, vocab_slice = plane.run_model(CFG)
    assert held == (0, 16) and vocab_slice == (0, 25024)
    assert m["num_experts"] == 128  # the router keeps its width


# -- the seeded weights ---------------------------------------------------
def _tiny():
    cfg = {**CFG, **{k: {**CFG[k], **v}
                     for k, v in CFG["rehearsal"].items()}}
    return plane.run_model(cfg)


def test_seeded_weights_are_reproducible_and_of_the_stated_spread():
    m, held, vocab_slice = _tiny()
    a = wts.params(m, held[1], vocab_slice[1], 2**31 + 5, 0.02)
    b = wts.params(m, held[1], vocab_slice[1], 2**31 + 5, 0.02)
    c = wts.params(m, held[1], vocab_slice[1], 6, 0.02)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a["head"], c["head"])
    assert abs(float(jnp.std(a["head"])) - 0.02) < 2e-3
    dense, expert = a["layers"][0], a["layers"][1]
    assert "router" not in dense and set(wts.DENSE) <= set(dense)
    assert expert["e_gate"].shape == (held[1], m["hidden_size"],
                                      m["moe_intermediate_size"])
    assert expert["router"].shape == (m["hidden_size"], 8)
    assert float(expert["q_norm"].min()) == 1.0
    assert not np.asarray(wts.zero_bias(m)).any()
    bias = wts.check_bias(m, 6)
    assert bias.shape == (2, 8) and float(jnp.abs(bias).min()) > 0
    np.testing.assert_array_equal(bias, wts.check_bias(m, 6))


# -- the reference against a second computation of one layer -------------
def test_the_reference_layer_is_the_equations_worked_another_way():
    """One expert layer of the reference against plain numpy, float64:
    token by token, head by head, expert by expert, no blocks, no
    scan."""
    m, held, vocab_slice = _tiny()
    p = jax.tree.map(lambda x: np.asarray(x, np.float64) * (
        6 if x.ndim >= 2 else 1), wts.params(m, held[1], vocab_slice[1], 3,
                                             0.02)["layers"][1])
    T, D = 12, m["hidden_size"]
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    rng = np.random.default_rng(0)
    x = rng.normal(size=(T, D))
    bias = rng.normal(size=(8,)) * 0.3
    eps, W = m["rms_norm_eps"], m["sliding_window"]

    def rms(v, g):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * g

    def rope(v):  # [T, heads, hd], the two halves against each other
        half = hd // 2
        inv = 1.0 / (m["rope_theta"] ** (np.arange(half) / half))
        ang = np.arange(T)[:, None] * inv[None]
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        a, b = v[..., :half], v[..., half:]
        return np.concatenate([a * c - b * s, a * s + b * c], -1)

    def silu(v):
        return v / (1 + np.exp(-v))

    a = rms(x, p["in_norm"])
    q = rope(rms((a @ p["wq"]).reshape(T, H, hd), p["q_norm"]))
    k = rope(rms((a @ p["wk"]).reshape(T, KV, hd), p["k_norm"]))
    v = (a @ p["wv"]).reshape(T, KV, hd)
    o = np.zeros((T, H, hd))
    for i in range(T):
        for h in range(H):
            js = [j for j in range(T) if i - W < j <= i]
            s = np.array([q[i, h] @ k[j, h // (H // KV)] for j in js])
            s = np.exp(s / math.sqrt(hd) - (s / math.sqrt(hd)).max())
            o[i, h] = sum(w * v[j, h // (H // KV)]
                          for w, j in zip(s / s.sum(), js))
    o = o.reshape(T, H * hd) / (1 + np.exp(-(a @ p["w_gate_attn"])))
    x1 = x + rms(o @ p["wo"], p["post_attn_norm"])
    mm = rms(x1, p["pre_mlp_norm"])
    scores = 1 / (1 + np.exp(-(mm @ p["router"])))
    f = silu(mm @ p["s_gate"]) * (mm @ p["s_up"]) @ p["s_down"]
    counts = np.zeros(8, int)
    for i in range(T):
        pick = np.argsort(-(scores[i] + bias), kind="stable")[
            :m["num_experts_per_tok"]]
        counts[pick] += 1
        for e in pick:
            if held[0] <= e < held[0] + held[1]:
                j = e - held[0]
                w = scores[i, e] / (scores[i, pick].sum() + 1e-20) \
                    * m["route_scale"]
                f[i] += w * (silu(mm[i] @ p["e_gate"][j])
                             * (mm[i] @ p["e_up"][j])) @ p["e_down"][j]
    want = x1 + rms(f, p["post_mlp_norm"])
    got, got_counts = ref.layer_fn(
        jnp.asarray(x, jnp.float32), jax.tree.map(
            lambda t: jnp.asarray(t, jnp.float32), p),
        jnp.asarray(bias, jnp.float32), "sliding_attention", m, held,
        lambda t: t, False)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_array_equal(got_counts, counts)
    # the control that lets the window see everything is another result
    off, _ = ref.layer_fn(
        jnp.asarray(x, jnp.float32), jax.tree.map(
            lambda t: jnp.asarray(t, jnp.float32), p),
        jnp.asarray(bias, jnp.float32), "sliding_attention", m, held,
        lambda t: t, True)
    assert float(jnp.max(jnp.abs(off - got))) > 1e-3


def test_the_bias_rule_by_hand():
    counts = np.array([[4, 0, 2, 2], [1, 1, 1, 1]])
    out = ref.bias_rule(np.zeros((2, 4), np.float32), counts, 0.5)
    # mean 2: d = (-1, +1, 0, 0), centred already; a level row stays
    np.testing.assert_allclose(out, [[-0.5, 0.5, 0, 0], [0, 0, 0, 0]])
    out = ref.bias_rule(np.zeros((1, 4), np.float32), [[5, 1, 1, 1]], 1.0)
    # d = (-1, 1, 1, 1), mean 0.5
    np.testing.assert_allclose(out, [[-1.5, 0.5, 0.5, 0.5]])


# -- the counts, by hand ---------------------------------------------------
def test_needed_flops_at_the_cells_widths():
    nf = manifest.needed_flops("train_window_moe")
    w = nf.matmul_weights(CFG)
    attn = 2048 * 4096 * 3 + 2 * 2048 * 512           # q, gate, o; k, v
    assert w["attention"] == 5 * attn == 5 * 27_262_976
    assert w["dense"] == 3 * 2048 * 6144
    assert nf.reached(CFG) == 1.0                      # 8 x 16 / 128
    assert w["experts"] == 4 * (2048 * 128 + 2 * 3 * 2048 * 1024)
    assert w["head"] == 25024 * 2048
    assert round((w["layers"] + w["head"]) / 1e6, 1) == 276.7
    mix = manifest.traffic(MIX)
    pairs = nf.attention_pairs(CFG, 8192)
    assert pairs["full"] == 8192 * 8193 / 2
    assert pairs["window"] == 2048 * 2049 / 2 + (8192 - 2048) * 2048
    per_pair = 3 * 2 * 32 * (128 + 128)                # fwd + 2 x bwd
    want = 6 * (w["layers"] + w["head"]) + per_pair * (
        4 * pairs["window"] + pairs["full"]) / 8192
    assert nf.token_flops(CFG, mix) == pytest.approx(want)
    assert 2.1e9 < want < 2.3e9                        # ~2.2 GFLOP a token
    assert nf.request_flops(CFG, mix, 8192, 0, {}) == pytest.approx(
        want * 8192)
    # a window that holds the whole sequence is the full layer
    assert nf.attention_pairs({"model": {"sliding_window": 64}}, 16) == {
        "full": 136.0, "window": 136.0}


def test_roofline_counts_by_hand():
    # 4 tokens, window 2: rows see 1, 2, 2, 2 keys
    assert rl.pairs(4, 2) == 7 and rl.pairs(4) == 10 and rl.pairs(4, 9) == 10
    f = rl.flash_fwd(2, 8, 2, 4, 16, window=2)
    assert f["flops"] == 2 * 2 * 2 * 8 * 7 * 16
    assert f["bytes"] == 2 * 4 * 16 * 2 * (2 * 8 + 2 * 2)
    b = rl.flash_bwd(2, 8, 2, 4, 16, window=2)
    assert b["flops"] == 5 * f["flops"] / 2
    assert b["bytes"] == 2 * 4 * 16 * 2 * (4 * 8 + 4 * 2)
    m = {"layer_types": ["sliding_attention", "full_attention"],
         "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
         "sliding_window": 2}
    both = rl.step_calls(m, 2, 4, rl.flash_fwd)
    assert both["flops"] == f["flops"] + rl.flash_fwd(2, 8, 2, 4, 16)["flops"]
    g = rl.gmm(100, 2, 4, 8, 16)
    assert g["flops"] == 12 * 100 * 8 * 16
    assert g["bytes"] == 100 * (32 + 128) * 2      # no matrix reads
    t = rl.tgmm(100, 2, 4, 8, 16)
    assert t["flops"] == 6 * 100 * 8 * 16
    assert t["bytes"] == 2 * 3 * 4 * 8 * 16 * 4 + 100 * 3 * 24 * 2


# -- the readers ------------------------------------------------------------
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(traced=True):
    steps, calls = 100, 10
    train = {
        "steps": steps, "tokens_per_step": 8192, "elapsed_s": 25.0,
        "step_metrics": {
            "held_pairs": [30000.0] * 40 + [34000.0] * 60,
            "expert_load_max": [1024.0] * steps,
            "expert_load_mean": [512.0] * steps,
            "bias_abs_max": [0.01] * steps, "grad_norm": [1.0] * steps},
        "traced_steps": list(range(40, 50)),
        "spans": {"step": [0.25] * steps, "data": [0.002] * steps,
                  "report": [0.001] * steps}}
    if traced:
        k = lambda s, n: {"seconds": 2.5, "calls": calls,  # noqa: E731
                          "op_seconds": s, "op_calls": n}
        train["trace"] = {
            "devices": 1, "idle_share": 0.01,
            "kernels": {"afmoe_flash_fwd": k(0.2, 50),
                        "afmoe_flash_bwd": k(0.6, 100),
                        "afmoe_gmm": k(0.3, 360), "afmoe_tgmm": k(0.1, 120),
                        "flash_fwd": k(0.2, 50), "flash_bwd": k(0.3, 50)},
            "scopes": {"programs_s": 2.5, "program_calls": calls,
                       "moe_router": 0.1, "moe_routed": 0.5,
                       "moe_shared": 0.15, "attn_window": 0.8,
                       "attn_full": 0.3, "optimizer": 0.2}}
    return {"plane": "train", "cell": manifest.cell(CELL), "config": CFG,
            "traffic": manifest.traffic(MIX), "peaks": PEAKS, "train": train}


def test_every_reader_on_a_recorded_context():
    ctx, m = _ctx(), CFG["model"]
    read = lambda n: manifest.layer_metric(n).read(ctx)  # noqa: E731
    nf = manifest.needed_flops("train_window_moe")
    rate = 100 * 8192 / 25.0
    assert read("afmoe_train_mfu") == pytest.approx(
        100 * rate * nf.token_flops(CFG, ctx["traffic"]) / 197e12)
    fwd = rl.step_calls(m, 1, 8192, rl.flash_fwd)
    assert read("afmoe_flash_fwd_roofline") == pytest.approx(
        100 * (10 * fwd["flops"] / 197e12) / 0.2)
    bwd = rl.step_calls(m, 1, 8192, rl.flash_bwd)
    assert read("afmoe_flash_bwd_roofline") == pytest.approx(
        100 * (10 * bwd["flops"] / 197e12) / 0.6)
    # the traced steps' own pairs: steps 40-49 are the 34,000s
    g = rl.gmm(34000.0 * 10, 40, 16, 2048, 1024)
    assert read("afmoe_gmm_roofline") == pytest.approx(
        100 * max(g["flops"] / 197e12, g["bytes"] / 819e9) / 0.3)
    t = rl.tgmm(34000.0 * 10, 40, 16, 2048, 1024)
    assert read("afmoe_tgmm_roofline") == pytest.approx(
        100 * max(t["flops"] / 197e12, t["bytes"] / 819e9) / 0.1)
    assert read("afmoe_moe_device_share") == pytest.approx(30.0)
    assert read("afmoe_attn_device_share") == pytest.approx(44.0)
    assert read("afmoe_optimizer_device_share") == pytest.approx(8.0)
    assert read("afmoe_expert_load_max_over_mean") == pytest.approx(2.0)
    assert read("afmoe_held_pairs_per_token") == pytest.approx(
        (30000 * 40 + 34000 * 60) / 100 / 8192)
    for name in NEW_METRICS:
        v = read(name)
        assert v is not None and v > 0, name
        if name.endswith("_roofline") or name.endswith("_mfu"):
            assert v <= 100.0, name


def test_the_readers_return_none_where_there_is_nothing_of_theirs():
    untraced = _ctx(traced=False)
    from_trace = [n for n in NEW_METRICS
                  if manifest.layer_metric(n).SOURCE == "device_trace"]
    assert len(from_trace) == 7
    for name in NEW_METRICS:
        read = manifest.layer_metric(name).read
        v = read(untraced)
        assert (v is None) == (name in from_trace), name
        # off the train plane, and on GPT-2's cell
        assert read({"plane": "serve", "replicas": [], "client": {}}) is None
        gpt2 = {**_ctx(), "config": manifest.config("gpt2-medium"),
                "cell": manifest.cell("gpt2m_train_stream"),
                "traffic": manifest.traffic("train_stream")}
        assert read(gpt2) is None, name
    # a program without the scopes or the counters (the parent)
    bare = _ctx()
    bare["train"]["trace"].pop("scopes")
    bare["train"].pop("step_metrics")
    for name in ("afmoe_moe_device_share", "afmoe_gmm_roofline",
                 "afmoe_held_pairs_per_token",
                 "afmoe_expert_load_max_over_mean"):
        assert manifest.layer_metric(name).read(bare) is None, name
    # and GPT-2's own readers say nothing on this cell's context: the
    # manifest no longer hands it to them (`train_mfu` reads `n_embd`)
    mine = [p["name"] for p in manifest.metrics_for(CELL, "per_layer")]
    assert not set(GPT2_ONLY) & set(mine)
    # the one kernel reader every train cell shares reads this cell's
    # calls: a forward a layer against ONE call of the backward pair
    assert manifest.layer_metric("flash_fwd_calls_per_bwd").read(_ctx()) \
        == 1.0
    replayed = _ctx()
    replayed["train"]["trace"]["kernels"]["flash_fwd"]["op_calls"] = 100
    assert manifest.layer_metric("flash_fwd_calls_per_bwd").read(replayed) \
        == 2.0
    assert manifest.layer_metric("flash_fwd_calls_per_bwd").read(
        _ctx(traced=False)) is None


def test_a_scope_is_found_under_its_differentiated_forms():
    s = plane.Scope("attn_full")
    for part in ("attn_full", "jvp(attn_full)", "transpose(jvp(attn_full))"):
        assert s in ["jit(step)", part, "dot_general"]
    for part in ("attn_full_x", "jvp(attn_window)", "attn", "jvp()"):
        assert s not in ["jit(step)", part]
    assert {s: 1.0}["attn_full"] == 1.0 and str(s) == "attn_full"
    assert set(plane.SCOPES) >= {"attn_window", "attn_full", "moe_routed",
                                 "optimizer", "router_bias", "lm_head"}


def test_the_kernels_are_told_by_name_or_result_and_never_as_gpt2s():
    """`flash_fwd` / `flash_bwd` (what `flash_fwd_calls_per_bwd` reads in
    every train cell) are the forward and ONE call of the backward pair."""
    preds = plane.kernel_predicates(CFG, manifest.traffic(MIX))
    assert set(preds) == {"afmoe_flash_fwd", "afmoe_flash_bwd", "afmoe_gmm",
                          "afmoe_tgmm", "flash_fwd", "flash_bwd"}
    shared = {"afmoe_flash_fwd": ["flash_fwd"], "dkv": ["flash_bwd"]}
    line = ("%{} = {} custom-call(%a, %b), "
            "custom_call_target=\"tpu_custom_call\"")
    out, kv = "bf16[4,8,8192,128]{3,2,1,0:T(8,128)(2,1)}", "bf16[4,8192,128]{2,1,0}"
    hits = {
        # by the name a `named_scope` leaves it, and inside a transform's
        ("flash_fwd_grouped.6", "(s32[1], s32[2])"): "afmoe_flash_fwd",
        ("jvp_flash_fwd_grouped_.1", "(s32[1], s32[2])"): "afmoe_flash_fwd",
        ("transpose_jvp_flash_bwd_dq_grouped__.1", "s32[1]"):
            "afmoe_flash_bwd",
        ("flash_bwd_dkv_grouped", "s32[1]"): "dkv",
        ("grouped_matmul_prefetch.12", "s32[1]"): "afmoe_gmm",
        ("tgmm", "s32[1]"): "afmoe_tgmm", ("tgmm.3", "s32[1]"): "afmoe_tgmm",
        # by what it returns, whatever wraps it
        ("checkpoint.4", f"({out}, f32[4,32,1,2048]{{3,2,1,0:T(1,128)}})"):
            "afmoe_flash_fwd",
        ("rematted_computation.2", out): "afmoe_flash_bwd",
        ("closed_call.9", f"({kv}, {kv})"): "dkv",
        ("checkpoint.7", "bf16[16384,1024]{1,0:T(8,128)(2,1)}"): "afmoe_gmm",
        ("checkpoint.8", "bf16[16384,2048]{1,0}"): "afmoe_gmm",
        ("closed_call.1", "f32[16,2048,1024]{2,1,0}"): "afmoe_tgmm",
        ("closed_call.2", "f32[16,1024,2048]{2,1,0}"): "afmoe_tgmm",
    }
    for (op, res), label in hits.items():
        want = [label.replace("dkv", "afmoe_flash_bwd")] + shared.get(label, [])
        assert [k for k, p in preds.items()
                if p(line.format(op, res))] == want, (op, res)
    # GPT-2's calls, a fusion, another product's name: none of ours
    for op, res in (("flash_fwd.2", "(bf16[256,1024,64], f32[256,1024,1])"),
                    ("flash_bwd_fused", "(bf16[256,1024,64], bf16[256,1024,64],"
                                        " bf16[256,1024,64])"),
                    ("atgmm.1", "s32[1]"), ("gmm.1", "bf16[128,1024]")):
        assert not any(p(line.format(op, res)) for p in preds.values()), op
    assert not preds["afmoe_tgmm"]("%tgmm.1 = f32[2] add(%a, %b)")


@pytest.mark.parametrize("fwd_a_layer", [1, 2], ids=["kept", "replayed"])
def test_forward_calls_a_backward_from_a_trace_of_this_cell(fwd_a_layer):
    """Ops as the chip prints them, counted by this plane's predicates
    as a traced run counts them: five layers a step, the backward a
    pair of calls a layer, of which `flash_bwd` counts one."""
    from types import SimpleNamespace as NS

    from benchmarks import trace_reduce

    out, kv = "bf16[4,8,8192,128]{3,2,1,0:T(8,128)(2,1)}", \
        "bf16[4,8192,128]{2,1,0:T(8,128)(2,1)}"

    def call(name, n, res):
        return (f"%{name}.{n} = {res} custom-call(%q, %k, %v), "
                'custom_call_target="tpu_custom_call"')

    fwd = lambda n: call(  # noqa: E731
        "flash_fwd_grouped", n, f"({out}, f32[4,32,1,2048]{{3,2,1,0:T(1,128)}})")
    dq = lambda n: call("flash_bwd_dq_grouped", n, out)  # noqa: E731
    dkv = lambda n: call("flash_bwd_dkv_grouped", n, f"({kv}, {kv})")  # noqa: E731
    ns = lambda n, s, d: NS(name=n, start_ns=s * 1e6, duration_ns=d * 1e6)  # noqa: E731
    ops, t = [], 0.0
    for step in range(3):
        for layer in range(5):
            ops.append(ns(fwd(layer), t, 4.0))
            t += 5.0
        for layer in range(5):
            if fwd_a_layer == 2:
                ops.append(ns(fwd(5 + layer), t, 4.0))
                t += 5.0
            ops += [ns(dq(layer), t, 3.5), ns(dkv(layer), t + 4.0, 4.5)]
            t += 10.0
    prof = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops),
        NS(name="XLA Modules", events=[ns("jit_step", 0.0, t)])])])
    kernels = {k: trace_reduce.programs_containing(prof, pred) for k, pred
               in plane.kernel_predicates(CFG, manifest.traffic(MIX)).items()}
    assert kernels["afmoe_flash_bwd"]["op_calls"] == 30
    assert kernels["flash_bwd"]["op_calls"] == 15
    ctx = {"plane": "train",
           "train": {"trace": {"devices": 1, "kernels": kernels}}}
    assert manifest.layer_metric("flash_fwd_calls_per_bwd").read(ctx) \
        == float(fwd_a_layer)


# -- the manifest -----------------------------------------------------------
def check_the_manifest_finds_every_new_file():
    """What PR 61 added is held BY NAME: where in its list an entry
    stands, and what follows it, is the next PR's to change."""
    man = manifest.manifest()
    cell = manifest.cell(CELL)
    assert cell in man["workloads"] and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (NAME, MIX)
    entry = next(c for c in man["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    assert os.path.exists(os.path.join(manifest.REPO, entry["file"]))
    assert os.path.exists(os.path.join(manifest.REPO,
                                       CFG["reference"]["file"]))
    e2e = [e["name"] for e in manifest.metrics_for(CELL, "end_to_end")]
    assert e2e == ["train_tokens_per_s", "setup_s"]
    tps = next(e for e in man["end_to_end"]
               if e["name"] == "train_tokens_per_s")
    at = tps["workloads"].index(CELL)
    assert tps["workloads"][:at] == ["gpt2m_train_stream"]
    per_layer = manifest.metrics_for(CELL, "per_layer")
    names = [p["name"] for p in per_layer]
    listed = [p["name"] for p in man["per_layer"] if p["name"] in NEW_METRICS]
    assert tuple(listed) == NEW_METRICS
    assert [n for n in names if n in NEW_METRICS] == list(NEW_METRICS)
    # the train plane's own readers read this cell unchanged
    assert {"train_step_ms", "train_data_wait_ms", "train_report_stall_ms",
            "device_idle_share.train", "flash_fwd_calls_per_bwd"} <= set(names)
    assert not set(GPT2_ONLY) & set(names)
    for p in man["per_layer"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL] and \
                p["moves"] == "train_tokens_per_s"
            mod = manifest.layer_metric(p["name"])
            assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
                p["layer"], p["unit"], p["source"], p["moves"]), p["name"]
        elif p["name"] in GPT2_ONLY:
            assert p["workloads"][0] == "gpt2m_train_stream"
            assert CELL not in p["workloads"]
    assert "workloads" not in next(
        p for p in man["per_layer"] if p["name"] == "flash_fwd_calls_per_bwd")
    assert manifest.needed_flops("train_window_moe").token_flops
    assert len(json.dumps(man, indent=1)) < 64 * 1024
    assert len(man["per_layer"]) <= 128


def test_the_manifest_finds_every_new_file():
    check_the_manifest_finds_every_new_file()


def test_the_parent_fails_at_once_on_the_missing_model(monkeypatch):
    monkeypatch.setattr(plane, "NEED", (("models", "no_such_model.py"),))
    with pytest.raises(RuntimeError, match="cannot run"):
        plane.run(manifest.cell(CELL), CFG, manifest.traffic(MIX), None, 0.0)


def _sound(held=4.0, n=40):
    """What a sound run hands `verdict`: 4.0 held pairs a token is what
    a balanced router sends 16 of 128 experts at top-8 in 4 layers."""
    t = {"check": {"grad_rel_err": 0.001, "held_grad_rel_err": 0.001,
                   "loss_abs_diff": 0.0001, "bias_off_share": 0.0,
                   "sample_held_pairs_per_token": 4.1},
         "losses": [math.log(25024) + 0.1, 9.0], "steps": 5,
         "tokens_per_step": 8192,
         "step_metrics": {"held_pairs": [held * 8192] * n}}
    return t, {"train": t, "reported_steps": 5, "reported_with_counters": 5}


def test_the_verdict_names_every_row_and_fails_on_any():
    t, ctx = _sound()
    v = plane.verdict(ctx, CFG)
    assert v["correct"] and len(v["rows"]) == 11
    assert len({name for name, _, _ in v["rows"]}) == 11
    for change in ({"reported_steps": 4}, {"reported_with_counters": 4}):
        assert not plane.verdict({**ctx, **change}, CFG)["correct"]
    for key in ("grad_rel_err", "held_grad_rel_err", "loss_abs_diff",
                "bias_off_share"):
        bad = {**t, "check": {**t["check"], key: 1.0}}
        assert not plane.verdict({**ctx, "train": bad}, CFG)["correct"], key
    flat = {**t, "losses": [10.2, 10.2]}
    assert not plane.verdict({**ctx, "train": flat}, CFG)["correct"]
    nan = {**t, "losses": [10.2, float("nan")]}
    assert not plane.verdict({**ctx, "train": nan}, CFG)["correct"]


@pytest.mark.parametrize("held_pairs, row", [
    # the lr sweep's readings (chip, PR 61): at 1e-6 the window's mean
    # was 3.37 a token, at 3e-4 0.07, and both ran FASTER
    ([3.37 * 8192] * 40, "window_held_pairs_off_balance_share"),
    ([0.07 * 8192] * 40, "window_held_pairs_off_balance_share"),
    # a collapse in the window's last steps alone
    ([4.0 * 8192] * 200 + [0.0] * 10,
     "last_steps_held_pairs_off_balance_share"),
    # experts that take MORE than a balanced share are as wrong
    ([4.6 * 8192] * 40, "window_held_pairs_off_balance_share"),
    ([], "window_held_pairs_off_balance_share"),
], ids=["drifting", "collapsed", "collapsing-late", "overloaded", "no-steps"])
def test_a_routing_that_leaves_the_held_experts_idle_is_not_correct(
        held_pairs, row):
    t, ctx = _sound()
    bad = {**t, "step_metrics": {"held_pairs": held_pairs}}
    v = plane.verdict({**ctx, "train": bad}, CFG)
    assert not v["correct"]
    assert [n for n, x, l in v["rows"] if not x <= l][0] == row


@pytest.mark.parametrize("sample", [0.0, 3.0, 5.5])
def test_a_sample_that_gave_the_held_experts_no_rows_is_not_correct(sample):
    """Before any balancing a seed's sample read anywhere from ~0 to 5.5
    held pairs a token: the held experts' backward was compared on
    whatever rows the seed happened to give."""
    t, ctx = _sound()
    bad = {**t, "check": {**t["check"], "sample_held_pairs_per_token": sample}}
    assert not plane.verdict({**ctx, "train": bad}, CFG)["correct"]


def test_the_window_of_a_sound_run_is_inside_the_band():
    """3.95-4.04 over a window, 3.96-4.02 over its last ten steps, single
    steps 3.5-4.46 (chip, PR 61, three seeds at lr 1e-7)."""
    for mean in (3.95, 4.04):
        assert plane.verdict(_sound(mean)[1], CFG)["correct"]
    t, ctx = _sound()
    noisy = {**t, "step_metrics": {"held_pairs": [
        x * 8192 for x in [3.5, 4.46] * 20]}}
    assert plane.verdict({**ctx, "train": noisy}, CFG)["correct"]


def test_the_cells_rehearsal_leaves_nothing_running():
    import test_bench_guard as guard

    proc, mark = guard.start(CELL)
    out, err = proc.communicate(timeout=900)
    assert proc.returncode == 3, err[-3000:]
    assert "rehearsal passed" in err
    assert '"correct"' not in out.strip().splitlines()[-1]
    assert '"metrics"' not in out
    # the rows of `correct` are the run's last lines on standard error
    for row in ("grad_rel_err_vs_reference",
                "bias_entries_off_the_rule_share",
                "reports_without_counters"):
        assert f'"{row}"' in err
    guard.assert_clean(mark)
