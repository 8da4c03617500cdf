"""The power-retention configuration: the system against its plain
reference at tiny sizes on the CPU (logits), the seeded weights, the
controls, the counts of its rooflines at the cell's shapes, its
readers, the configuration's file, and its cell's rehearsal."""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, roofline_retention
from benchmarks import weights_brumby as wts
from benchmarks.planes import serve_retention as plane
from benchmarks.reference import brumby as ref

CFG = manifest.config("brumby-14b-base-l5")
TINY = {**CFG["model"], **CFG["rehearsal"]["model"]}
# wide enough that logits spread (std ~1) at width 64
ASSUMED = {**CFG["assumed"], "initializer_range": 0.2}
CHUNK = 8


def _case(dtype, seed=4, T=21):
    mcfg = plane.model_config(TINY, ASSUMED, dtype)
    params = wts.params(TINY, ASSUMED, seed, dtype)
    toks = np.random.default_rng(seed).integers(1, TINY["vocab_size"], size=T)
    return mcfg, params, toks


def _reference_logits(toks, seed, dtype=jnp.float32, quant=None):
    kw = ref.layer_kwargs(TINY, ASSUMED)
    if quant is not None:
        kw["quant"] = quant
    with jax.default_matmul_precision("highest"):
        return ref.forward(
            jnp.asarray(toks),
            [wts.layer(TINY, ASSUMED, seed, l, dtype)
             for l in range(TINY["num_hidden_layers"])],
            wts.ends(TINY, ASSUMED, seed, dtype), **kw)


def _engine_logits(mcfg, params, toks, prompt_len, **kw):
    """Prefill then decode through the ENGINE's own program and its
    per-slot cache: the packed prefill as admission calls it (one
    prompt, into slot 1), then the model's decode step (what the chunk
    program scans) on the leaves the engine allocated, teacher-forced."""
    from ray_tpu.models import brumby
    from ray_tpu.serve.llm_engine import LlamaEngine, _Plan

    eng = LlamaEngine(mcfg, params, slots=2, chunk=2, block_size=CHUNK,
                      max_len=64, **kw)
    try:
        N = -(-prompt_len // CHUNK) * CHUNK
        N = next(n for n in eng._pack_sizes if n >= N)
        plan = _Plan({"stop": len(toks)}, 1, list(toks[:prompt_len]), [], [])
        out = eng._prefill_packed_for(N)(
            params, *eng._cache, *eng._pack_arrays(N, [plan]),
            eng._pos, eng._tok, eng._stop)
        cache, (pos, tok, stop) = tuple(out[:-3]), out[-3:]
        assert int(pos[1]) == prompt_len and int(stop[1]) == len(toks)
        first = int(tok[1])                 # the greedy pick, on the device
        logits = []
        for t in range(prompt_len, len(toks)):
            lg, cache = brumby.decode_step(
                mcfg, params, jnp.asarray([0, toks[t]]), cache,
                jnp.asarray([0, t]), live=jnp.asarray([False, True]),
                **eng._model._kw())
            logits.append(np.asarray(lg[1]))
        return first, np.stack(logits)
    finally:
        eng.shutdown()


def test_the_reference_imports_nothing_from_the_program():
    src = inspect.getsource(ref)
    assert "import ray_tpu" not in src and "from ray_tpu" not in src
    # the quadratic form: no monomials, no carried state, no chunks
    code = src.split('"""', 2)[2]
    assert "phi" not in code and "roll" not in code and "scan(" in code
    assert "def retention" in code and "s * s" in code


def test_layer_from_seed_equals_the_stacked_tree():
    tree = wts.params(TINY, ASSUMED, 9, jnp.float32)
    for l in range(TINY["num_hidden_layers"]):
        one = wts.layer(TINY, ASSUMED, 9, l, jnp.float32)
        assert set(one) == set(tree["blocks"]) == set(wts.LEAVES)
        for k, v in one.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(tree["blocks"][k][l]))
    served = wts.params(TINY, ASSUMED, 9)
    assert served["blocks"]["wg"].dtype == served["blocks"]["bg"].dtype \
        == jnp.float32 and served["blocks"]["wq"].dtype == jnp.bfloat16
    bg = np.asarray(tree["blocks"]["bg"])
    assert 4.6 <= bg.min() and bg.max() <= 6.9 and bg.std() > 0
    ends = wts.ends(TINY, ASSUMED, 9, jnp.float32)
    np.testing.assert_array_equal(np.asarray(ends["tok_emb"]),
                                  np.asarray(tree["tok_emb"]))
    big = wts.layer(TINY, ASSUMED, 2**31 + 5, 1, jnp.float32)
    assert not np.array_equal(
        np.asarray(big["wq"]),
        np.asarray(wts.layer(TINY, ASSUMED, 5, 1, jnp.float32)["wq"]))


def test_model_forward_equals_the_reference_float32():
    """Logits of the chunked-scan forward against the reference's
    quadratic form, float32 on both sides.  2e-3 on logits of std ~1:
    summation order (8-token chunks and a carried state against whole
    rows of scores) through two layers."""
    from ray_tpu.models import brumby

    mcfg, params, toks = _case(jnp.float32)
    want = np.asarray(_reference_logits(toks, 4))
    with jax.default_matmul_precision("highest"):
        got, _ = brumby.forward(mcfg, params, jnp.asarray([toks]),
                                chunk=CHUNK)
    assert want.std() > 0.3
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-3, rtol=0)
    assert (np.asarray(got[0]).argmax(-1) == want.argmax(-1)).all()


@pytest.mark.parametrize("kw", [{}, {"decode_kernel": "pallas",
                                     "kernel_interpret": True}],
                         ids=["xla", "pallas-interpret"])
def test_engine_prefill_then_decode_equals_the_reference_float32(kw):
    """Logits, float32 on both sides: the engine's packed prefill, the
    state it leaves in the slot and the decode steps off it against the
    reference's FULL forward over the same tokens.  2e-3 on logits of
    std ~1, as above: what is left is summation order (a state of 144
    monomials a head against rows of scores)."""
    mcfg, params, toks = _case(jnp.float32)
    want = np.asarray(_reference_logits(toks, 4))
    with jax.default_matmul_precision("highest"):
        first, got = _engine_logits(mcfg, params, toks, prompt_len=11, **kw)
    assert first == int(want[10].argmax())
    np.testing.assert_allclose(got, want[11:], atol=2e-3, rtol=0)
    assert (got.argmax(-1) == want[11:].argmax(-1)).all()


def _mean_margin(served_logits, reference_logits):
    served = jnp.asarray(served_logits.argmax(-1))
    ref_l = jnp.asarray(reference_logits)
    picked = jnp.take_along_axis(ref_l, served[:, None], axis=-1)[:, 0]
    return float(jnp.mean(ref_l.max(-1) - picked))


def test_bfloat16_as_served_stays_close_and_the_control_fails():
    """As served (bfloat16 weights and compute, float32 gate and state)
    against the float32 reference on the SAME bfloat16 weights: the
    median logit error under 0.05 on logits of std ~1 (bfloat16 keeps 8
    bits), and the MARGIN of the served token under the reference, what
    `correct` compares, far below the control's (the reference with
    every matmul operand of the layers rounded to float8): a limit
    between them fails the control."""
    from benchmarks.reference import precision
    from ray_tpu.models import brumby

    mcfg, params, toks = _case(jnp.bfloat16, T=32)
    want = np.asarray(_reference_logits(toks, 4, jnp.bfloat16))
    got, _ = brumby.forward(mcfg, params, jnp.asarray([toks]), chunk=CHUNK)
    got = np.asarray(got[0])
    assert np.median(np.abs(got - want)) < 0.05
    sound = _mean_margin(got, want)
    control = _mean_margin(np.asarray(_reference_logits(
        toks, 4, jnp.bfloat16, quant=precision.fp8_e4m3)), want)
    assert sound < 0.02 and control > 3 * max(sound, 0.01), (sound, control)


def test_head_margins_in_vocabulary_blocks_equal_the_whole_head():
    ends = wts.ends(TINY, ASSUMED, 3, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (12, TINY["hidden_size"]))
    served = jnp.asarray(np.random.default_rng(0).integers(0, 256, size=12))
    with jax.default_matmul_precision("highest"):
        logits = ref.head(x, ends["final_norm"], ends["lm_head"], 1e-6)
        marg, std = ref.head_margins(x, ends["final_norm"], ends["lm_head"],
                                     1e-6, served, 64)
    picked = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    np.testing.assert_allclose(marg, logits.max(-1) - picked, atol=1e-5)
    assert float(std) == pytest.approx(float(jnp.std(logits)), rel=1e-4)
    assert CFG["model"]["vocab_size"] % CFG["reference"]["vocab_block"] == 0


def test_the_controls():
    params = wts.params(TINY, ASSUMED, 3)
    ctl = plane.fp8_weights(wts.params(TINY, ASSUMED, 3))  # donates its own
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        a, b = params["blocks"][name], ctl["blocks"][name]
        assert a.dtype == b.dtype and not jnp.array_equal(a, b), name
        a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(a32 - b32)) / jnp.max(jnp.abs(a32)))
        assert 0 < rel < 0.07, name   # e4m3: 3 mantissa bits
    for name in ("wg", "bg", "attn_norm", "q_norm", "k_norm", "mlp_norm"):
        assert jnp.array_equal(ctl["blocks"][name], params["blocks"][name])
    for name in ("tok_emb", "lm_head", "final_norm"):
        assert jnp.array_equal(ctl[name], params[name])
    # the second control's rounding is the cast's, done on the bits
    x = jnp.concatenate([
        jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 100.0,
        jnp.asarray([1.0, 0.0, -3.0, 1.00390625, 1.01171875, 2.0 ** -20])])
    np.testing.assert_array_equal(
        np.asarray(plane.round_bf16(x)),
        np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))


def test_the_second_control_rounds_what_the_kernels_leave_and_take_in(
        monkeypatch):
    """`--control state_bf16` on the program's three entry points: the
    layer's state and key sum come back from the flush as bfloat16
    values (and no other layer's), and a reading step sees its held
    log-gates rounded; the state it reads was rounded by its writer."""
    from ray_tpu.ops import retention as ret

    for name in ("retention_decode", "retention_prefill", "retention_read"):
        monkeypatch.setattr(ret, name, getattr(ret, name))  # put back after
    plain_read = ret.retention_read
    plane.hold_state_in_bf16()
    B, H, KV, d, L = 2, 4, 2, 8, 2
    key = jax.random.split(jax.random.PRNGKey(5), 8)
    q, k, v = (jax.random.normal(key[i], (B, n, d)) + 0.5
               for i, n in enumerate((H, KV, KV)))
    g = -jnp.abs(jax.random.normal(key[3], (B, KV))) * 0.3
    shapes = ret.state_shapes(L, B, KV, d)
    state, keysum = (jax.random.normal(key[4 + i], sh) for i, sh in
                     enumerate(shapes))
    live, is_bf16 = jnp.ones((B,), bool), lambda x: bool(jnp.array_equal(  # noqa: E731
        x, x.astype(jnp.bfloat16).astype(jnp.float32)))
    _, st, zs = ret.retention_decode(q, k, v, g, state, keysum, live, 1,
                                     eps=1e-6)
    assert is_bf16(st[1]) and is_bf16(zs[1]) and not is_bf16(state[1])
    np.testing.assert_array_equal(np.asarray(st[0]), np.asarray(state[0]))
    held = ret.Pending(k[:, :, None], v[:, :, None], g[:, :, None] * 1.37,
                       jnp.asarray(1, jnp.int32))
    got, _ = ret.retention_read(q, k, v, g, st, zs, held, live, 1, eps=1e-6)
    raw, _ = plain_read(q, k, v, g, st, zs, held, live, 1, eps=1e-6)
    want, _ = plain_read(q, k, v, g, st, zs,
                         held._replace(G=plane.round_bf16(held.G)), live, 1,
                         eps=1e-6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got), np.asarray(raw))


def test_roofline_counts_at_the_cells_shapes():
    m = CFG["model"]
    H, KV, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    assert roofline_retention.monomials(d) == 8256
    # 8 KV heads x (8,256 x 128 + 8,256) float32: 34.08 MB a layer and row
    assert roofline_retention.state_bytes(KV, d) == 8 * 8256 * 129 * 4 \
        == 34_080_768
    w = roofline_retention.retention_decode(32, H, KV, d)
    io = (2 * 40 + 2 * 8) * 128 * 2
    assert w["bytes"] == 32 * (2 * 34_080_768 + io)
    assert w["flops"] == 32 * 8 * 8256 * 129 * (3 + 2 * 5)
    peaks = manifest.peaks("TPU v5 lite")
    least = roofline_retention.least_seconds(w, peaks)
    assert least["bound"] == "memory" and 2.6e-3 < least["seconds"] < 2.7e-3
    # a step that writes no state reads each live row's once: half the
    # bytes but for q, k, v, o, and the read-out's 2 operations alone
    r = roofline_retention.retention_read(28, H, KV, d)
    assert r["bytes"] == 28 * (34_080_768 + io)
    assert r["flops"] == 28 * 8 * 8256 * 129 * 2 * 5
    least = roofline_retention.least_seconds(r, peaks)
    assert least["bound"] == "memory" and 1.16e-3 < least["seconds"] < 1.17e-3
    # a 2,048-token prompt alone, 256-token chunks: 8 chunks, 7 carried
    p = roofline_retention.retention_prefill(2048, 1, 256, H, KV, d)
    pairs = 8 * 256 * 257 / 2
    assert p["flops"] == (40 * 4 * 128 * pairs
                          + 40 * 2 * 8256 * 129 * 7 * 256
                          + 8 * 2 * 8256 * 129 * 2048)
    assert p["bytes"] == 2048 * io + 34_080_768
    assert roofline_retention.least_seconds(p, peaks)["bound"] == "compute"
    # linear in tokens and prompts: means over calls are exact
    q = roofline_retention.retention_prefill(1024, 2, 256, H, KV, d)
    two = roofline_retention.retention_prefill(512, 1, 256, H, KV, d)
    assert q["flops"] == pytest.approx(2 * two["flops"])


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(json.loads(l) for l in f
                       if l.startswith('{"name": "Brumby-14B-Base"'))
    m = CFG["model"]
    if row is not None:
        assert CFG["source"] == row["source_url"]
        assert {k: v for k, v in m.items() if k != "num_hidden_layers"} == \
            {k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
        assert row["config"]["num_hidden_layers"] == 40
    assert m["num_hidden_layers"] == 5 and CFG["reduced"] == ["num_hidden_layers"]
    assert all(CFG[k] == v for k, v in m.items())     # the top-level copy
    a, e = CFG["assumed"], CFG["engine"]
    assert a["degree"] == 2 and a["retention_eps"] == 1e-6
    assert a["gate_bias"] == [4.6, 6.9] and "float32" in a["state_dtype"]
    assert e["slots"] == 32 and e["chunk"] == 8 and e["max_len"] == 2320
    assert e["prefix_cache"] is False and "kv_blocks" not in e
    layer = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8
             + 3 * 5120 * 17408)
    weights = 2 * (5 * layer + 2 * 151936 * 5120)
    assert 6.40e9 < weights < 6.43e9
    from ray_tpu.ops import retention

    held = sum(4 * int(np.prod(s)) for s in retention.state_shapes(
        5, e["slots"], 8, 128))
    assert 5.48e9 < held < 5.51e9 and weights + held > 0.25 * 16e9
    mix = manifest.traffic("batch_closed_1k_a128")
    assert mix["clients"] == 48 and mix["output_len"] == {"fixed": 128}
    assert max(mix["prompt_len"]["choices"]) + 128 < e["max_len"]
    assert all(p % e["block_size"] == 0 for p in mix["prompt_len"]["choices"])


def _ctx(scopes=None, ticks=(), kernels=None):
    return {"plane": "serve", "config": CFG,
            "peaks": manifest.peaks("TPU v5 lite"),
            "replicas": [{
                "rid": "1", "tick_ring": list(ticks),
                "engine": {"prefill_calls": 10, "prefill_rows": 20,
                           "prefill_tokens": 20480},
                "trace": {"devices": 1, "scopes": scopes or {},
                          "kernels": kernels or {}}}]}


def test_the_new_readers_and_what_they_return_on_the_parent():
    tick = {"active": 32, "state_rows_live": 30, "row_steps_live": 224,
            "row_steps": 256}
    call = {"seconds": 2.0, "calls": 10, "op_seconds": 1.6,
            "op_calls": 10 * 8 * 5}
    kernels = {"paged_decode": call, "retention_decode": call,
               "retention_prefill": {"seconds": 1.0, "calls": 10,
                                     "op_seconds": 0.1, "op_calls": 50}}
    scopes = {"programs_s": 2.0, "program_calls": 10, "retention_attn": 1.5,
              "dense_mlp": 0.4}
    ctx = _ctx(scopes, [tick, {"active": 0}], kernels)
    read = lambda n: manifest.layer_metric(n).read(ctx)  # noqa: E731
    assert read("retention_device_share") == pytest.approx(75.0)
    assert read("engine_state_rows_live") == pytest.approx(30.0)
    # 28 live rows x 68.2 MB at 819 GB/s = 2.33 ms; traced 4 ms a call
    assert 57 < read("retention_decode_roofline") < 60
    # a program that writes at every step has no kernel that only reads
    assert read("retention_read_roofline") is None
    # 2,048 tokens in 2 prompts a call: 171 GFLOP = 0.87 ms; traced 2 ms
    assert 42 < read("retention_prefill_roofline") < 45
    assert read("decode_step_ms") == pytest.approx(1e3 * 2.0 / (8 * 10))
    parent = _ctx({}, [{"active": 32}])
    for name in ("retention_device_share", "engine_state_rows_live",
                 "retention_decode_roofline", "retention_read_roofline",
                 "retention_prefill_roofline"):
        assert manifest.layer_metric(name).read(parent) is None, name


def test_the_flush_is_counted_at_its_own_rows_and_the_reads_at_theirs():
    """A chunk of 8 steps that 30 rows began and 26 ended (four stopped
    after their 4th step): 224 live row-steps, of which the last step's
    26 are WRITTEN and the seven steps before it read 198, 28.3 a call.
    One flush and seven reads a layer and chunk program."""
    tick = {"active": 32, "state_rows_live": 30, "state_rows_flushed": 26,
            "row_steps_live": 224, "row_steps": 256}
    flush = {"seconds": 2.0, "calls": 10, "op_seconds": 0.15,
             "op_calls": 10 * 5}
    reads = {"seconds": 2.0, "calls": 10, "op_seconds": 0.49,
             "op_calls": 10 * 7 * 5}
    kernels = {"paged_decode": flush, "retention_decode": flush,
               "retention_read": reads}
    ctx = _ctx({}, [tick, {"active": 0}], kernels)
    read = lambda n: manifest.layer_metric(n).read(ctx)  # noqa: E731
    io = (2 * 40 + 2 * 8) * 128 * 2
    # 26 rows x 68.2 MB at 819 GB/s = 2.165 ms; traced 3 ms a call
    assert read("retention_decode_roofline") == pytest.approx(
        100 * 26 * (2 * 34_080_768 + io) / 819e9 / 3e-3)
    # 198 / 7 rows x 34.1 MB = 1.178 ms; traced 1.4 ms a call
    assert read("retention_read_roofline") == pytest.approx(
        100 * (198 / 7) * (34_080_768 + io) / 819e9 / 1.4e-3)
    assert 84 < read("retention_read_roofline") < 85
    # one flush a chunk program: the program's time over its 8 steps
    assert read("decode_step_ms") == pytest.approx(1e3 * 2.0 / (8 * 10))
    # a plane that does not ship what was flushed (this benchmark's
    # parent): both at the chunk's mean, 28 rows, as before
    old = _ctx({}, [{k: v for k, v in tick.items()
                     if k != "state_rows_flushed"}], kernels)
    assert manifest.layer_metric("retention_decode_roofline").read(old) == \
        pytest.approx(100 * 28 * (2 * 34_080_768 + io) / 819e9 / 3e-3)
    assert manifest.layer_metric("retention_read_roofline").read(old) == \
        pytest.approx(100 * 28 * (34_080_768 + io) / 819e9 / 1.4e-3)
    assert "state_rows_flushed" in plane.STATE_KEYS


def test_kernel_predicates_tell_the_three_kernels_apart():
    pred = plane.kernel_predicates(CFG)
    decode = ("%closed_call.9 = (f32[32,8,128,128]{3,2,1,0}, "
              "f32[32,8,8,128]{3,2,1,0}, f32[5,32,8,65,128,128]{5,4,3,2,1,0}, "
              "f32[5,32,8,65,128]{4,3,2,1,0}) custom-call(s32[1] %a), "
              "custom_call_target=\"tpu_custom_call\"")
    # the denominators first, as the program's `retention_read` gives them
    reading = ("%retention_read.6 = (f32[32,8,8,128]{3,2,1,0:T(8,128)S(1)}, "
               "f32[32,8,128,128]{3,2,1,0:T(8,128)S(1)}) custom-call(s32[1] "
               "%a), custom_call_target=\"tpu_custom_call\"")
    prefill = ("%closed_call.3 = (bf16[8,5,2048,128]{3,2,1,0}, "
               "f32[5,32,8,65,128,128]{5,4,3,2,1,0}, f32[5,32,8,65,128]"
               "{4,3,2,1,0}) custom-call(s32[1] %a), "
               "custom_call_target=\"tpu_custom_call\"")
    fusion = "%fusion.3 = f32[32,8,128,128]{3,2,1,0} fusion(f32[32] %x)"
    assert set(pred) == {"paged_decode", "retention_decode",
                         "retention_read", "retention_prefill"}
    for line, names in ((decode, {"paged_decode", "retention_decode"}),
                        (reading, {"retention_read"}),
                        (prefill, {"retention_prefill"}), (fusion, set())):
        assert {n for n, p in pred.items() if p(line)} == names, line


def test_the_cells_rehearsal_leaves_nothing_running():
    import test_bench_guard as guard

    proc, mark = guard.start("brumby14b_batch_closed_1k")
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 3, err[-3000:]
    assert "rehearsal passed" in err
    assert '"correct"' not in out.strip().splitlines()[-1]
    assert '"metrics"' not in out
    guard.assert_clean(mark)
