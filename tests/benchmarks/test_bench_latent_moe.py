"""The latent-attention / mixture-of-experts configuration: the system
against its plain reference at tiny sizes on the CPU, the control shown
to fail, the seeded weights, the counts of its rooflines at the cell's
shapes, its readers, and its cell's rehearsal."""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, roofline_latent_moe, trace_scopes
from benchmarks import weights_deepseek_v3 as wts
from benchmarks.planes import serve_latent_moe as plane
from benchmarks.reference import deepseek_v3 as ref

CFG = manifest.config("kanana-2-30b-a3b-l7")
TINY = {**CFG["model"], **CFG["rehearsal"]["model"]}
STD = 0.2   # wide enough that logits spread (std ~1) at width 64


def _case(dtype, seed=4, T=20):
    mcfg = plane.model_config(TINY, dtype)
    params = wts.params(TINY, seed, dtype, std=STD)
    toks = np.random.default_rng(seed).integers(1, TINY["vocab_size"], size=T)
    return mcfg, params, toks


def _reference_logits(toks, seed, quant=None, dtype=jnp.float32):
    kw = ref.layer_kwargs(TINY)
    if quant is not None:
        kw["quant"] = quant
    ends = wts.ends(TINY, seed, dtype, std=STD)
    x = ref.embed(jnp.asarray(toks), ends["tok_emb"])
    for l in range(TINY["num_hidden_layers"]):
        x = ref.layer(x, wts.layer(TINY, seed, l, dtype, std=STD), **kw)
    return ref.head(x, ends["final_norm"], ends["lm_head"],
                    TINY["rms_norm_eps"], **(
                        {"quant": quant} if quant is not None else {}))


def _engine_logits(mcfg, params, toks, prompt_len):
    """Prefill then decode through the ENGINE's own programs and its
    latent pool: the prefill and block-write programs as `_admit` calls
    them, then the model's paged decode step (what the chunk program
    scans) on the pool the engine allocated, teacher-forced."""
    from ray_tpu.models import deepseek_v3 as m
    from ray_tpu.serve.llm_engine import LlamaEngine

    eng = LlamaEngine(mcfg, params, slots=2, chunk=2, block_size=8,
                      max_len=64, decode_kernel="pallas",
                      kernel_interpret=True)
    try:
        i32 = jnp.int32
        bucket = 16
        padded = list(toks[:prompt_len]) + [0] * (bucket - prompt_len)
        logits, lat = eng._prefill_for(bucket)(
            params, jnp.asarray([padded], i32))
        out = [np.asarray(logits[:prompt_len])]
        blocks = [1, 2, 3, 4]
        pool, _, _ = eng._write_blocks_for(bucket, 2)(
            *eng._cache, lat, jnp.asarray(blocks[:2], i32),
            jnp.asarray(0, i32), jnp.asarray(prompt_len, i32),
            jnp.asarray(0, i32), eng._pos, eng._tok)
        assert pool.shape[-1] == 128 and pool.shape[0] == mcfg.n_layers
        tables = jnp.asarray([blocks, [0, 0, 0, 0]], i32)
        for t in range(prompt_len, len(toks)):
            lg, pool, _ = m.decode_step(
                mcfg, params, jnp.asarray([toks[t], 0], i32), pool,
                jnp.asarray([t, 0], i32), tables=tables, interpret=True)
            out.append(np.asarray(lg[:1]))
        return np.concatenate(out)
    finally:
        eng.shutdown()


def test_the_reference_imports_nothing_from_the_program():
    src = inspect.getsource(ref)
    assert "import ray_tpu" not in src and "from ray_tpu" not in src
    assert "ragged" not in src and "argsort" not in src  # no sorting either


def test_layer_from_seed_equals_the_stacked_tree():
    tree = wts.params(TINY, 9, jnp.float32, std=STD)
    n_dense = TINY["first_k_dense_replace"]
    for l in range(TINY["num_hidden_layers"]):
        one = wts.layer(TINY, 9, l, jnp.float32, std=STD)
        stack = tree["dense_layers"] if l < n_dense else tree["moe_layers"]
        assert set(one) == set(stack)
        for k, v in one.items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(stack[k][l - (l >= n_dense) * n_dense]))
    assert tree["moe_layers"]["router"].dtype == jnp.float32
    assert wts.params(TINY, 9)["moe_layers"]["router"].dtype == jnp.float32
    assert wts.params(TINY, 9)["moe_layers"]["e_gate"].dtype == jnp.bfloat16
    assert float(jnp.std(tree["moe_layers"]["router_bias"])) > 0  # path runs
    big = wts.layer(TINY, 2**31 + 5, 1, jnp.float32)
    assert not np.array_equal(np.asarray(big["wq"]),
                              np.asarray(wts.layer(TINY, 5, 1, jnp.float32)["wq"]))


def test_engine_prefill_then_decode_equals_the_reference_float32():
    """Logits, float32 on both sides: the engine's expanded prefill,
    its latent pool and the absorbed paged decode against the
    reference's full forward.  1e-3 on logits of std ~1: what is left
    is summation order (blockwise softmax, grouped products)."""
    mcfg, params, toks = _case(jnp.float32)
    want = np.asarray(_reference_logits(toks, 4))
    got = _engine_logits(mcfg, params, toks, prompt_len=11)
    assert want.std() > 0.3
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=0)
    assert (got.argmax(-1) == want.argmax(-1)).all()


def _mean_margin(served_logits, reference_logits):
    served = jnp.asarray(served_logits.argmax(-1))
    return float(jnp.mean(ref.margins(jnp.asarray(reference_logits), served)))


def test_bfloat16_as_served_stays_close_and_the_control_fails():
    """As served (bfloat16 weights, cache and compute, float32 router)
    against the float32 reference on the SAME bfloat16 weights.  The
    tolerance is on the MEDIAN logit error, 0.05 on logits of std 1.56
    (bfloat16 keeps 8 bits), and not on the largest: where the system's
    bfloat16 hidden state puts a token's second and third expert the
    other way round, a whole expert's output is swapped and that
    position's logits move by up to ~3 (two of 24 positions here).
    That is also why `correct` compares the MARGIN of the served token
    under the reference, which a flip moves little: the sound system
    reads 0.017 here, and the control, the reference with every matmul
    operand rounded to float8, 0.59; a limit between them fails it."""
    from benchmarks.reference import precision

    mcfg, params, toks = _case(jnp.bfloat16, T=24)
    want = np.asarray(_reference_logits(toks, 4, dtype=jnp.bfloat16))
    got = _engine_logits(mcfg, params, toks, prompt_len=11)
    err = np.abs(got - want)
    assert np.median(err) < 0.05 and (err.max(-1) > 0.5).sum() <= 4, (
        np.median(err), err.max(-1))
    sound = _mean_margin(got, want)
    control = _mean_margin(np.asarray(_reference_logits(
        toks, 4, quant=precision.fp8_e4m3, dtype=jnp.bfloat16)), want)
    limit = 0.1
    assert sound < limit / 2 and control > 2 * limit, (sound, control)


def test_the_controls_rounding_is_float8_e4m3():
    x = jnp.concatenate([
        jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 100.0,
        jnp.asarray([448.0, -448.0, 1.0, 0.0, 2.0 ** -6, 3.0 * 2.0 ** -9])])
    want = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    got = plane.round_e4m3(x)
    # the cast rounds ties to even mantissas, `round` to even multiples:
    # the same thing; everything must agree
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(jnp.max(jnp.abs(got - x) / jnp.maximum(jnp.abs(x), 1e-3))) \
        <= 2.0 ** -4


def test_the_planes_control_rounds_the_layers_matmul_weights_only():
    params = wts.params(TINY, 3, std=STD)
    ctl = plane.fp8_weights(wts.params(TINY, 3, std=STD))  # donates its own
    for stack, names in (("moe_layers", ("e_gate", "e_down", "s_up", "wq",
                                         "wkv_b")),
                         ("dense_layers", ("w_gate", "wo"))):
        for name in names:
            a, b = params[stack][name], ctl[stack][name]
            assert a.dtype == b.dtype and not jnp.array_equal(a, b), name
            a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
            rel = float(jnp.max(jnp.abs(a32 - b32)) / jnp.max(jnp.abs(a32)))
            assert 0 < rel < 0.07, name   # e4m3: 3 mantissa bits
    for name in ("router", "router_bias", "attn_norm", "kv_norm"):
        assert jnp.array_equal(ctl["moe_layers"][name],
                               params["moe_layers"][name]), name
    for name in ("tok_emb", "lm_head", "final_norm"):
        assert jnp.array_equal(ctl[name], params[name])


def test_roofline_counts_at_the_cells_shapes():
    m, e = CFG["model"], CFG["engine"]
    live = 64 * 1400
    w = roofline_latent_moe.mla_decode(live, e["slots"], 32, 576, 512)
    assert w["bytes"] == live * 1152 + 64 * 32 * (576 + 512) * 2
    assert w["flops"] == 2 * 32 * 1088 * live
    assert 55 < w["flops"] / w["bytes"] < 65       # ~60 flop/B: memory-bound
    pairs = e["slots"] * m["num_experts_per_tok"]
    r = roofline_latent_moe.moe_routed(pairs, 0.95 * 6 * 128, 6, 2048, 768)
    assert pairs == 384
    assert r["flops"] == 2 * 3 * 2048 * 768 * 384 * 6
    expert_bytes = 3 * 2048 * 768 * 2
    assert 0.99 < (0.95 * 768 * expert_bytes) / r["bytes"] <= 1.0
    peaks = manifest.peaks("TPU v5 lite")
    assert roofline_latent_moe.least_seconds(r, peaks)["bound"] == "memory"
    assert 8.0e-3 < roofline_latent_moe.least_seconds(r, peaks)["seconds"] < 8.8e-3


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(json.loads(l) for l in f
                       if l.startswith('{"name": "kanana-2-30b-a3b'))
    m = CFG["model"]
    if row is not None:
        assert CFG["source"] == row["source_url"]
        assert {k: v for k, v in m.items() if k != "num_hidden_layers"} == \
            {k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
        assert row["config"]["num_hidden_layers"] == 48
    assert m["num_hidden_layers"] == 7 and CFG["reduced"] == ["num_hidden_layers"]
    assert all(CFG[k] == v for k, v in m.items())     # the top-level copy
    e = CFG["engine"]
    per_token = 7 * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * 2
    assert per_token == 8064
    assert e["kv_blocks"] * e["block_size"] >= e["slots"] * 2304
    expert_layer = 128 * 3 * 2048 * 768 + 26.35e6 + 9.44e6 + 0.26e6
    total = 2 * (2 * 128256 * 2048 + 64.1e6 + 6 * expert_layer)
    assert 8.8e9 < total < 8.95e9
    assert total + e["kv_blocks"] * 16 * 7 * 640 * 2 > 0.25 * 16e9


def test_scope_seconds_reads_the_name_stack_out_of_a_recorded_trace():
    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "tiny_v5e.xplane.pb")
    got = trace_scopes.scope_seconds(fixture, ("jit(step)", "moe_routed"),
                                     ("jit_step",))
    assert got["program_calls"] == 60 and got["programs_s"] > 0
    assert 0 < got["jit(step)"] <= got["programs_s"]
    assert "moe_routed" not in got           # a program without the scope
    none = trace_scopes.scope_seconds(fixture, ("jit(step)",), ("jit_other",))
    assert none["program_calls"] == 0 and "jit(step)" not in none


def _ctx(scopes=None, ticks=()):
    return {"plane": "serve", "config": CFG,
            "peaks": manifest.peaks("TPU v5 lite"),
            "replicas": [{"rid": "1", "tick_ring": list(ticks),
                          "trace": {"devices": 1, "scopes": scopes or {},
                                    "kernels": {"paged_decode": {
                                        "seconds": 1.2, "calls": 10,
                                        "op_seconds": 0.12,
                                        "op_calls": 80 * 7}}}}]}


def test_the_new_readers_and_what_they_return_on_the_parent():
    tick = {"active": 64, "live_tokens": 64 * 1400, "experts_touched": 730.0,
            "experts_total": 768, "expert_load_max": 9}
    scopes = {"programs_s": 1.2, "program_calls": 10, "moe_router": 0.05,
              "moe_routed": 0.8, "moe_shared": 0.05, "mla_attn": 0.2}
    ctx = _ctx(scopes, [tick, {"active": 0, "live_tokens": 0}])
    read = lambda n: manifest.layer_metric(n).read(ctx)  # noqa: E731
    assert read("moe_device_share") == pytest.approx(75.0)
    assert read("moe_expert_load_max_over_mean") == pytest.approx(3.0)
    # 730 experts x 9.44 MB at 819 GB/s = 8.4 ms a step; traced 10 ms
    assert 80 < read("moe_routed_roofline") < 90
    # 89.6k live tokens x 1,152 B = 103 MB = 126 us; traced 214 us a call
    assert 55 < read("mla_decode_roofline") < 62
    parent = _ctx({}, [{"active": 64, "live_tokens": 1000}])
    for name in ("moe_device_share", "moe_expert_load_max_over_mean",
                 "moe_routed_roofline"):
        assert manifest.layer_metric(name).read(parent) is None, name
    mistral = {**parent, "config": manifest.config("mistral-7b-v0.3-l16")}
    assert manifest.layer_metric("mla_decode_roofline").read(mistral) is None


def test_kernel_predicates_point_decode_step_ms_at_the_latent_kernel():
    pred = plane.kernel_predicates(CFG)
    attn = ("%closed_call.9 = bf16[64,32,512]{2,1,0} custom-call(s32[1] %a), "
            "custom_call_target=\"tpu_custom_call\"")
    gmm = ("%closed_call.3 = bf16[384,768]{1,0} custom-call(bf16[384,2048] %x),"
           " custom_call_target=\"tpu_custom_call\"")
    append = ("%closed_call.1 = bf16[7,9217,16,640] custom-call(s32[1] %a), "
              "custom_call_target=\"tpu_custom_call\", "
              "output_to_operand_aliasing={{}: (3, {})}")
    assert pred["paged_decode"](attn) and not pred["paged_decode"](gmm)
    assert pred["moe_grouped"](gmm) and not pred["moe_grouped"](attn)
    assert pred["paged_append"](append) and not pred["paged_decode"](append)
    assert manifest.layer_metric("decode_step_ms").read(_ctx()) == \
        pytest.approx(1e3 * 1.2 / (8 * 10))


def test_the_cells_rehearsal_leaves_nothing_running():
    import test_bench_guard as guard

    proc, mark = guard.start("kanana2_batch_closed_1k")
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 3, err[-3000:]
    assert "rehearsal passed" in err
    assert '"correct"' not in out.strip().splitlines()[-1]
    assert '"metrics"' not in out
    guard.assert_clean(mark)
