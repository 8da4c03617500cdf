"""The hybrid decoder (`models/lfm2.py`: gated short convolutions beside
grouped-query attention, sigmoid-routed experts) against its plain
reference (`benchmarks/reference/lfm2.py`) at tiny sizes on the CPU, and
through the engine: a cache of BOTH kinds in one spec."""

import dataclasses
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2 as ref
from ray_tpu.exceptions import PrefixCacheUnsupportedError
from ray_tpu.models import lfm2
from ray_tpu.models.llama import Packed
from ray_tpu.ops import paged_attention as pa
from ray_tpu.parallel import moe
from ray_tpu.serve.engine_model import engine_model_for
from ray_tpu.serve.kv_cache import BlockPool
from ray_tpu.serve.llm_engine import LlamaEngine

CFG = lfm2.Lfm2MoeConfig.tiny()
# heads of 64: side by side in one pool row of whole lanes (`kv_pool_tail`)
WIDE = dataclasses.replace(
    CFG, n_heads=4, n_kv_heads=2, head_dim=64,
    layer_types=("conv", "full_attention", "conv"), n_dense_layers=1)
# float32 on both sides; the reduction orders differ (a grouped product
# against experts one at a time, an online softmax against a dense one)
TOL = 2e-4


def _params(cfg, seed=0):
    # wide enough that logits spread (std ~1) at width 64
    return lfm2.init_params(cfg, jax.random.PRNGKey(seed), std=0.2)


def _layers(cfg, params):
    """The reference's way in: one dict a layer, out of the stacks."""
    out, seen = [], {"conv": 0, "attn": 0, "dense": 0, "moe": 0}
    for l, kind in enumerate(cfg.layer_types):
        op = "conv" if kind == "conv" else "attn"
        ffn = "dense" if l < cfg.n_dense_layers else "moe"
        w = {k: v[seen[op]] for k, v in params[op].items()}
        w.update({k: v[seen[ffn]] for k, v in params[ffn].items()})
        seen[op] += 1
        seen[ffn] += 1
        out.append(w)
    return out


def _reference_logits(cfg, params, toks):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            jnp.asarray(toks), _layers(cfg, params), params,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            theta=cfg.rope_theta, eps=cfg.norm_eps, top_k=cfg.top_k,
            scale=cfg.routed_scale))


def _tokens(n, seed=1, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


def _greedy(cfg, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        lg, _, _ = lfm2.forward(cfg, params, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(lg[0, -1])))
    return toks[len(prompt):]


def _pack(prompts, N, bs, K):
    """A packed row as the engine's `_pack_arrays` lays it out."""
    tokens, posn = np.zeros(N, np.int32), np.zeros(N, np.int32)
    seg = np.full(N, -1, np.int32)
    last, starts, at = np.zeros(K, np.int32), [], 0
    for i, p in enumerate(prompts):
        T = len(p)
        tokens[at:at + T], seg[at:at + T] = p, i
        posn[at:at + T] = np.arange(T)
        last[i] = at + T - 1
        starts.append(at)
        at += -(-T // bs) * bs
    return jnp.asarray(tokens), Packed(jnp.asarray(last), jnp.asarray(seg),
                                       jnp.asarray(posn)), starts


def test_the_layer_runs_and_the_cache_spec():
    assert lfm2.layer_runs(CFG) == [
        ("conv", "dense", 0, 0, 2), ("attn", "moe", 0, 0, 1),
        ("conv", "moe", 2, 1, 2), ("attn", "moe", 1, 3, 1)]
    full = lfm2.Lfm2MoeConfig()
    assert (full.n_layers, full.n_conv_layers, full.n_attn_layers) == (24, 18, 6)
    cut = dataclasses.replace(full, layer_types=lfm2.LAYER_TYPES[:16])
    assert (cut.n_conv_layers, cut.n_attn_layers, cut.n_moe_layers) == (12, 4, 14)
    model = engine_model_for(cut, kv_dtype="model", block_size=16, chunk=8,
                             paged=False, interpret=False)
    # paged leaves first, each counting its own layers; a token's heads
    # of 64 side by side in one row of whole lanes
    assert [(l.name, l.per_slot, l.layers, l.tail)
            for l in model.cache_leaves] == [
        ("k", False, 4, (512,)), ("v", False, 4, (512,)),
        ("conv", True, 12, (6144,))]
    pool = BlockPool(10369, spec=model.cache_leaves, slots=128)
    assert pool.leaf_shapes(16, 16) == [
        ((4, 10369, 16, 512), jnp.bfloat16)] * 2 + [
        ((12, 128, 6144), jnp.bfloat16)]
    assert pool.bytes_per_token(16) == 8192          # 4 x 2 x 8 x 64 x 2 B
    assert pool.bytes_per_slot(16) == 147456         # 12 x 2048 x 3 x 2 B
    assert model.kv is not None and model.kv_dtype == "model"
    with pytest.raises(ValueError, match="int8"):
        engine_model_for(cut, kv_dtype="int8", block_size=16, chunk=8,
                         paged=False, interpret=False)
    assert pa.kv_pool_tail(8, 64) == (512,)
    assert pa.kv_pool_tail(8, 128) == (8, 128)
    assert pa.kv_pool_tail(2, 16) == (2, 16)         # 32 are no whole lanes


@pytest.mark.parametrize("cfg", [CFG, WIDE], ids=["tiny", "heads-of-64"])
def test_forward_equals_the_reference(cfg):
    params, toks = _params(cfg), _tokens(24)
    logits, (ks, vs), conv = lfm2.forward(cfg, params, jnp.asarray([toks]))
    want = _reference_logits(cfg, params, toks)
    assert float(np.std(want)) > 0.3
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=TOL)
    assert conv is None and ks.shape == (
        cfg.n_attn_layers, 1, 24, cfg.n_kv_heads, cfg.head_dim)


@pytest.mark.parametrize("route", ["dense", "paged-interpret"])
@pytest.mark.parametrize("cfg", [CFG, WIDE], ids=["tiny", "heads-of-64"])
def test_prefill_then_decode_through_both_caches_equals_the_reference(
        cfg, route):
    """The packed prefill as admission calls it (one prompt into slot
    1), then `decode_step` on the engine's own leaves, teacher-forced:
    every logit against the reference's FULL forward pass, which has no
    cache of either kind."""
    params, toks, T0, bs = _params(cfg), _tokens(23), 9, 8
    want = _reference_logits(cfg, params, toks)
    paged = route != "dense"
    eng = LlamaEngine(cfg, params, slots=2, chunk=2, block_size=bs,
                      max_len=40, decode_kernel="pallas" if paged else "gather",
                      kernel_interpret=paged)
    try:
        model, cache = eng._model, eng._cache
        tokens, packed, _ = _pack([toks[:T0]], 16, bs, 2)
        logits, (ks, vs), conv = lfm2.forward(
            cfg, params, tokens[None], cache[2], packed=packed,
            slots=jnp.asarray([1, 2], jnp.int32))   # 2: out of range
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[T0 - 1],
                                   atol=TOL)
        blocks = jnp.asarray([1, 2, 3], jnp.int32)   # slot 1's table
        k, v = model.kv.write(cache[:2], blocks[:2], (ks, vs))
        tables = jnp.zeros((2, 3), jnp.int32).at[1].set(blocks)
        cache = (k, v, conv)
        for t in range(T0, len(toks)):
            tok = jnp.asarray([0, toks[t]], jnp.int32)
            pos = jnp.asarray([0, t], jnp.int32)
            live = jnp.asarray([False, True])
            if paged:
                lg, cache, st = lfm2.decode_step(
                    cfg, params, tok, cache, pos, tables=tables, live=live,
                    interpret=True)
            else:
                view = (*model.kv.rows(cache[:2], tables), cache[2])
                lg, view, st = lfm2.decode_step(cfg, params, tok, view, pos,
                                                live=live)
                cache = (*model.kv.write(cache[:2], tables, view[:2]),
                         view[2])
            np.testing.assert_allclose(np.asarray(lg[1]), want[t], atol=TOL)
            assert int(st["experts_touched"]) == cfg.n_moe_layers * cfg.top_k
            assert int(st["load_max"]) == 1
    finally:
        eng.shutdown()


def test_prompts_packed_in_one_row_equal_each_alone():
    """Three prompts end to end, the second starting on the very token
    after the first's last (16 is whole blocks): a tap never crosses a
    segment, attention stays inside a prompt, a padding token routes
    nowhere; K and V rows, end states and logits equal each alone."""
    params, bs, K = _params(CFG), 8, 4
    prompts = [_tokens(16, 2), _tokens(5, 3), _tokens(11, 4)]
    tokens, packed, starts = _pack(prompts, 48, bs, K)
    assert starts == [0, 16, 24]
    conv0 = jnp.zeros((CFG.n_conv_layers, 3, CFG.conv_L * CFG.dim))
    slots = jnp.asarray([2, 0, 1, 3], jnp.int32)     # 3: out of range
    logits, (ks, vs), conv = lfm2.forward(
        CFG, params, tokens[None], conv0, packed=packed, slots=slots)
    for i, p in enumerate(prompts):
        t1, pk1, _ = _pack([p], 16, bs, 1)
        lg1, (k1, v1), c1 = lfm2.forward(
            CFG, params, t1[None], conv0[:, :1], packed=pk1,
            slots=jnp.asarray([0], jnp.int32))
        T, at = len(p), starts[i]
        np.testing.assert_allclose(logits[0, i], lg1[0, 0], atol=1e-5)
        np.testing.assert_allclose(ks[:, 0, at:at + T], k1[:, 0, :T],
                                   atol=1e-5)
        np.testing.assert_allclose(vs[:, 0, at:at + T], v1[:, 0, :T],
                                   atol=1e-5)
        np.testing.assert_allclose(conv[:, int(slots[i])], c1[:, 0],
                                   atol=1e-5)
        assert float(jnp.abs(c1).max()) > 0
    # the state is the last three `B * u`, zeros before a prompt's start
    short = lfm2.forward(CFG, params, _pack([[7, 9]], 8, bs, 1)[0][None],
                         conv0[:, :1], slots=jnp.asarray([0], jnp.int32),
                         packed=_pack([[7, 9]], 8, bs, 1)[1])[2]
    D = CFG.dim
    assert not np.asarray(short[:, 0, :D]).any()
    assert np.asarray(short[:, 0, D:]).all()


def test_a_dead_row_leaves_its_state_and_its_blocks_untouched():
    params = _params(CFG)
    k, v, conv = lfm2.init_cache(CFG, 3, 16)
    key = jax.random.PRNGKey(5)
    cache = (k + 1.0, v + 2.0, jax.random.normal(key, conv.shape))
    tok = jnp.asarray([5, 6, 7], jnp.int32)
    pos = jnp.asarray([3, 4, 5], jnp.int32)
    live = jnp.asarray([True, False, True])
    lg, new, st = lfm2.decode_step(CFG, params, tok, cache, pos, live=live)
    for old, now in zip(cache, new):
        np.testing.assert_array_equal(np.asarray(old[:, 1]),
                                      np.asarray(now[:, 1]))
        assert not np.array_equal(np.asarray(old[:, 0]), np.asarray(now[:, 0]))
    # a live row moved ONE K row, and rolled its state by one tap
    changed = np.asarray((new[0][:, 2] != cache[0][:, 2]).any(axis=(2, 3)))
    assert changed.sum(axis=1).tolist() == [1] * CFG.n_attn_layers
    D = CFG.dim
    np.testing.assert_array_equal(np.asarray(new[2][:, 0, :2 * D]),
                                  np.asarray(cache[2][:, 0, D:]))
    # two live rows x top_k pairs a layer
    assert int(st["experts_touched"]) <= 2 * CFG.top_k * CFG.n_moe_layers
    # and the live rows' logits do not depend on the dead row
    lg2, _, _ = lfm2.decode_step(CFG, params, tok.at[1].set(99), cache, pos,
                                 live=live)
    np.testing.assert_array_equal(np.asarray(lg)[[0, 2]],
                                  np.asarray(lg2)[[0, 2]])


def test_the_routers_choice_uses_the_bias_and_its_weights_do_not():
    E, D, N, K = 8, 16, 32, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    h = jax.random.normal(ks[0], (N, D))
    router = jax.random.normal(ks[1], (D, E))
    scores = jax.nn.sigmoid(jnp.dot(h, router, precision="highest"))
    # a bias that lifts expert 3 over everything: always chosen
    bias = jnp.zeros((E,)).at[3].set(10.0)
    w, idx = moe.sigmoid_topk_route(h, router, bias, K, 1.0, 1e-6)
    assert (np.asarray(idx) == 3).any(axis=1).all()
    w0, idx0 = moe.sigmoid_topk_route(h, router, jnp.zeros((E,)), K, 1.0, 1e-6)
    assert not (np.asarray(idx0) == 3).any(axis=1).all()
    # ... and weighted by its SCORE, not by score + bias
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        np.asarray(w), picked / (picked.sum(axis=1, keepdims=True) + 1e-6),
        rtol=1e-6)
    ref_w, ref_idx = ref.route(h, router, bias, K, 1.0)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref_w), rtol=1e-6)


def test_kananas_routing_is_bit_identical_with_its_epsilon_as_an_argument():
    """`sigmoid_topk_route` took `+ 1e-20` from its body; it is the
    caller's now (`deepseek_v3.ROUTE_EPS`), and nothing else moved."""
    from ray_tpu.models import deepseek_v3

    assert deepseek_v3.ROUTE_EPS == 1e-20 and CFG.route_eps == 1e-6
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    h = jax.random.normal(ks[0], (64, 32))
    router = jax.random.normal(ks[1], (32, 16))
    bias = jax.random.normal(ks[2], (16,)) * 0.02
    scores = jax.nn.sigmoid(jnp.dot(h, router, precision="highest"))
    _, idx = jax.lax.top_k(scores + bias, 6)
    old = jnp.take_along_axis(scores, idx, axis=-1)
    old = old / (jnp.sum(old, axis=-1, keepdims=True) + 1e-20) * 2.448
    w, got = moe.sigmoid_topk_route(h, router, bias, 6, 2.448,
                                    deepseek_v3.ROUTE_EPS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(old))


# ----------------------------------------------------------------------
# through the engine
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cfg,kw", [
    (CFG, {}),
    (WIDE, dict(decode_kernel="pallas", kernel_interpret=True)),
], ids=["gather", "kernels-interpreted-heads-of-64"])
def test_the_engines_greedy_output_equals_a_dedicated_generate(cfg, kw):
    params = _params(cfg)
    eng = LlamaEngine(cfg, params, slots=3, chunk=2, block_size=8,
                      max_len=48, **kw)
    try:
        prompts = [_tokens(n, seed=n) for n in (5, 9, 17, 3, 12)]
        futs = [eng.submit(p, 6) for p in prompts]
        for p, f in zip(prompts, futs):
            assert f.result(timeout=600) == _greedy(cfg, params, p, 6)
        st = eng.stats()
        # BOTH kinds cost something, and the spec says what
        H, La, Lc = cfg.n_kv_heads * cfg.head_dim, cfg.n_attn_layers, \
            cfg.n_conv_layers
        assert st["cache_bytes_per_token"] == La * 2 * H * 4
        assert st["cache_bytes_per_slot"] == Lc * cfg.conv_L * cfg.dim * 4
        assert st["prefill_rows"] == 5 and st["prefill_calls"] < 5
        ticks = [t for t in st["tick_ring"] if t["row_steps"]]
        assert all(t["gather_blocks"] > 0 for t in ticks)
        assert any(t["state_rows_live"] > 0 for t in ticks)
        fields = [t for t in ticks if "experts_total" in t]
        assert fields and all(
            t["experts_total"] == cfg.n_moe_layers * cfg.n_experts
            and 0 <= t["expert_load_max"] <= 3 for t in fields)
        names = sorted(fn.__name__ for fn in eng._chunk_cache.values())
        assert all(n.startswith("decode_chunk_w") for n in names)
    finally:
        eng.shutdown()


def test_admission_stops_at_the_slots_and_at_the_blocks(monkeypatch):
    monkeypatch.setenv("RT_ENGINE_TICK_RING", "4096")  # every tick of (b)
    params = _params(CFG)
    # (a) blocks: a request of 17 + 8 - 1 = 24 positions takes 3 blocks
    # of 8; 7 blocks hold two of them, and the third slot stays free
    eng = LlamaEngine(CFG, params, slots=3, chunk=2, block_size=8,
                      max_len=32, kv_blocks=7)
    try:
        with eng._lock:
            plans = [eng._plan(_tokens(17, s), 8, Future(), time.time())
                     for s in range(3)]
        assert [p is not None for p in plans] == [True, True, False]
        assert len(eng._free) == 1 and eng._pool.free_blocks == 1
        assert [len(p.own) for p in plans[:2]] == [3, 3]
        for p in plans[:2]:
            eng._release(p.slot, eng._active.pop(p.slot))
    finally:
        eng.shutdown()
    # (b) slots: blocks for five, slots for two; the others wait
    eng = LlamaEngine(CFG, params, slots=2, chunk=2, block_size=8,
                      max_len=48, kv_blocks=30)
    try:
        futs = [eng.submit(_tokens(9, s), 24) for s in range(5)]
        seen = []
        while not all(f.done() for f in futs):
            seen.append(eng.stats()["blocks_free"])
            time.sleep(0.01)
        assert min(seen) >= 30 - 2 * 4     # never short of blocks
        assert all(len(f.result()) == 24 for f in futs)
        # slots held, from the ticks' own record: what was admitted less
        # what gave its slot back (every request here hands its slot
        # over at the dispatch of its last chunk, and is `active` until
        # that chunk's harvest: `active` may read 3).  A slot given back
        # twice, or an admission past the free slots, reads over 2
        st = eng.stats()
        assert st["handoffs_total"] == 5
        held = np.cumsum([t["admitted"] - t["handed_off"]
                          for t in st["tick_ring"]])
        assert held.max() == 2 and held.min() >= 0 and held[-1] == 0
    finally:
        eng.shutdown()


def test_the_prefix_cache_is_refused_with_the_typed_error():
    params = _params(CFG)
    with pytest.raises(PrefixCacheUnsupportedError, match="per-slot state"):
        LlamaEngine(CFG, params, slots=2, chunk=2, block_size=8, max_len=32,
                    prefix_cache=True)
    eng = LlamaEngine(CFG, params, slots=2, chunk=2, block_size=8, max_len=32)
    try:
        assert eng._radix is None and eng._has_blocks and eng._has_state
        with pytest.raises(PrefixCacheUnsupportedError):
            eng._model.suffix_prefill(8, 1)
        with pytest.raises(PrefixCacheUnsupportedError):
            eng._model.kv_write(8, 1)
    finally:
        eng.shutdown()
