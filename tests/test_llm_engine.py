"""Continuous-batching engine correctness (CPU, tiny model).

The invariant: greedy decoding is deterministic and rows are
independent, so every request served by the shared-slot engine must
produce EXACTLY the tokens a dedicated `llama.generate` yields for
the same prompt — across mixed lengths, mixed budgets, concurrent
submission, slot reuse, queueing beyond the slot count, paged KV
block reuse, radix prefix-cache hits, and LRU eviction under
block-pool pressure (RT008: all prompt RNGs seeded).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.serve.kv_cache import BlockPool, RadixCache  # noqa: E402
from ray_tpu.serve.llm_engine import LlamaEngine  # noqa: E402


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _expected(cfg, params, prompt, n_new):
    out = llama.generate(
        cfg, params, jnp.asarray([prompt], jnp.int32), n_new
    )
    return [int(t) for t in np.asarray(out)[0]]


def test_engine_matches_dedicated_generate(model):
    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=4, max_len=64, chunk=4)
    try:
        rng = np.random.RandomState(0)
        reqs = []
        for i in range(11):  # > slots: exercises queueing + slot reuse
            T = int(rng.randint(1, 20))
            n_new = int(rng.randint(1, 12))
            prompt = [int(x) for x in rng.randint(
                0, cfg.vocab_size, size=T)]
            reqs.append((prompt, n_new,
                         eng.submit(prompt, n_new)))
        for prompt, n_new, fut in reqs:
            got = fut.result(timeout=120)
            assert got == _expected(cfg, params, prompt, n_new), (
                f"engine diverged for T={len(prompt)} n={n_new}"
            )
    finally:
        eng.shutdown()


def test_engine_validates_and_clamps(model):
    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=2, max_len=32, chunk=2)
    try:
        with pytest.raises(ValueError):
            eng.submit([], 4).result(timeout=10)
        with pytest.raises(ValueError):
            eng.submit(list(range(40)), 4).result(timeout=10)
        # budget clamped to the sequence cap: T=20 -> at most 11 new
        out = eng.submit(list(range(1, 21)), 500).result(timeout=120)
        assert len(out) == 32 - 1 - 20
        s = eng.stats()
        assert s["active"] == 0 and s["free_slots"] == 2
    finally:
        eng.shutdown()


# ----------------------------------------------------------------------
# paged KV + radix prefix cache
# ----------------------------------------------------------------------
def _prompts_with_shared_system_prompt(cfg, n, rng):
    """The consumer-scale shape: one shared system prompt + a short
    per-request user tail."""
    system = [int(x) for x in rng.randint(0, cfg.vocab_size, size=19)]
    out = []
    for _ in range(n):
        tail = [int(x) for x in rng.randint(
            0, cfg.vocab_size, size=int(rng.randint(1, 6)))]
        out.append(system + tail)
    return out


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_paged_engine_bit_identical_prefix_on_off(model, prefix_cache):
    """Shared-system-prompt workload: greedy outputs must match a
    dedicated `llama.generate` exactly, with the radix cache on (later
    requests skip the shared prefill) AND off (every request prefills
    its full prompt)."""
    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=4, max_len=64, chunk=4,
                      block_size=8, prefix_cache=prefix_cache)
    try:
        rng = np.random.RandomState(1)
        prompts = _prompts_with_shared_system_prompt(cfg, 9, rng)
        futs = [(p, 7, eng.submit(p, 7)) for p in prompts]
        for p, n_new, fut in futs:
            got = fut.result(timeout=120)
            assert got == _expected(cfg, params, p, n_new), (
                f"prefix_cache={prefix_cache} diverged for T={len(p)}"
            )
        s = eng.stats()
        if prefix_cache:
            # 19-token system prompt = 2 full 8-token blocks shared;
            # at least the later requests must have hit them
            assert s["prefix_hit_tokens"] >= 2 * 8
            assert 0.0 < s["prefix_hit_rate"] < 1.0
        else:
            assert s["prefix_hit_tokens"] == 0
        assert s["active"] == 0 and s["queued"] == 0
    finally:
        eng.shutdown()


def test_paged_engine_eviction_under_pool_pressure(model):
    """A pool too small to cache every distinct prompt forces LRU
    eviction of unpinned radix nodes; outputs stay exact and the pool
    never leaks blocks."""
    cfg, params = model
    # 12 blocks of 8 = 96 tokens of KV for 2 slots of max_len 48
    eng = LlamaEngine(cfg, params, slots=2, max_len=48, chunk=2,
                      block_size=8, kv_blocks=12)
    try:
        rng = np.random.RandomState(2)
        for round_ in range(3):
            prompts = [
                [int(x) for x in rng.randint(0, cfg.vocab_size, size=T)]
                for T in (17, 20, 19, 18)
            ]
            futs = [(p, 6, eng.submit(p, 6)) for p in prompts]
            for p, n_new, fut in futs:
                got = fut.result(timeout=120)
                assert got == _expected(cfg, params, p, n_new), (
                    f"round {round_} diverged for T={len(p)}"
                )
        s = eng.stats()
        # distinct 2-block prefixes * 3 rounds cannot all fit in 12
        # blocks alongside live sequences: eviction must have fired
        assert eng._radix.evicted_blocks > 0
        # no leaks: free + cached == capacity once all requests finish
        assert s["blocks_free"] + s["blocks_cached"] == s["blocks_total"]
        assert s["active"] == 0
    finally:
        eng.shutdown()


def test_paged_engine_rejects_pool_smaller_than_one_sequence(model):
    """The admission invariant rests on the pool always covering one
    max_len sequence; a budget below that must fail fast, not deadlock
    a request mid-queue."""
    cfg, params = model
    with pytest.raises(ValueError, match="kv_blocks"):
        LlamaEngine(cfg, params, slots=2, max_len=48, chunk=2,
                    block_size=8, kv_blocks=5)


def test_gather_width_tracks_live_tokens_not_pool_budget(model):
    """The paged claim itself, shape-level and deterministic: the
    chunk dispatch's gather width W (blocks per slot the compiled
    program attends over) depends on LIVE sequence lengths only.  An
    over-provisioned pool (1024-token budget) runs the SAME compiled
    programs as a workload-sized one — the measured ~20x ring tax
    cannot exist by construction.  The wall-clock counterpart is
    `python -m ray_tpu.scripts.perf --engine-trace` (PERF.md)."""
    cfg, params = model
    rng = np.random.RandomState(3)
    prompt = [int(x) for x in rng.randint(0, cfg.vocab_size, size=24)]
    widths = {}
    for label, kv_blocks in (("sized", 48 // 8 * 2), ("over", 128)):
        # budget 1024 tokens (128 blocks of 8) vs workload-sized 96
        eng = LlamaEngine(cfg, params, slots=2, max_len=48, chunk=4,
                          block_size=8, kv_blocks=kv_blocks)
        try:
            assert eng.submit(prompt, 8).result(timeout=120) == _expected(
                cfg, params, prompt, 8
            )
            widths[label] = eng.stats()["gather_blocks"]
            assert eng.stats()["blocks_total"] == kv_blocks
        finally:
            eng.shutdown()
    assert widths["sized"] == widths["over"] > 0
    # W covers the live sequence (24 prompt + 8 new -> 4 blocks of 8),
    # nowhere near the 128-block budget
    assert widths["over"] <= 8


# ----------------------------------------------------------------------
# kv_cache bookkeeping units
# ----------------------------------------------------------------------
def test_block_pool_alloc_free_accounting():
    pool = BlockPool(8)
    assert pool.capacity == 7
    got = pool.alloc(7)
    assert sorted(got) == list(range(1, 8))  # scratch block 0 reserved
    assert pool.alloc(1) is None
    pool.free(got[:3])
    assert pool.free_blocks == 3
    with pytest.raises(ValueError):
        pool.free([0])


def test_radix_cache_match_insert_evict():
    pool = BlockPool(16)
    cache = RadixCache(4, pool)
    toks = list(range(1, 14))  # 13 tokens -> 3 shareable 4-blocks
    blocks, path = cache.match(toks)
    assert blocks == [] and path == []
    own = pool.alloc(3)
    path, adopted = cache.insert(toks, path, own)
    assert adopted == own and cache.cached_blocks == 3
    # pinned: eviction must not touch the path
    assert cache.evict(10) == 0
    cache.release(path)
    # a second request re-matches the full prefix and re-pins it
    blocks2, path2 = cache.match(toks + [99])
    assert blocks2 == own
    assert cache.evict(10) == 0  # pinned again
    cache.release(path2)
    # unpinned now: leaves evict deepest-first until drained
    freed = cache.evict(2)
    assert freed == 2 and cache.cached_blocks == 1
    assert pool.free_blocks == pool.capacity - 1
    assert cache.evict(5) == 1 and cache.cached_blocks == 0


def test_prefix_cache_disabled_for_non_dense_attention(model):
    """forward_with_prefix mirrors DENSE attention numerics; under any
    other attention backend the engine must refuse prefix reuse rather
    than risk cache-on/cache-off greedy divergence."""
    cfg, params = model
    import dataclasses

    flash_cfg = dataclasses.replace(cfg, attention="flash")
    eng = LlamaEngine(flash_cfg, params, slots=2, max_len=32, chunk=2,
                      block_size=8)
    try:
        assert eng._radix is None
    finally:
        eng.shutdown()
    eng = LlamaEngine(cfg, params, slots=2, max_len=32, chunk=2,
                      block_size=8)
    try:
        assert eng._radix is not None  # dense keeps the cache
    finally:
        eng.shutdown()


# ----------------------------------------------------------------------
# dead rows: a slot whose position has reached its stop
# ----------------------------------------------------------------------
def _latent_model():
    from ray_tpu.models import deepseek_v3 as m

    cfg = m.DeepseekV3Config.tiny()
    params = m.init_params(cfg, jax.random.PRNGKey(0), std=0.2)
    return cfg, params


def _latent_greedy(cfg, params, prompt, n_new):
    from ray_tpu.models import deepseek_v3 as m

    seq = list(prompt)
    for _ in range(n_new):
        lg = m.forward(cfg, params, jnp.asarray([seq]))
        seq.append(int(jnp.argmax(lg[0, -1])))
    return seq[len(prompt):]


def _alone_int8(cfg, params, prompt, n_new):
    """int8 KV rounds the cache, so `generate` is no oracle for it: the
    request alone in an engine of one slot that steps one token a chunk
    (no neighbour, no step past a budget inside a chunk)."""
    eng = LlamaEngine(cfg, params, slots=1, chunk=1, block_size=8,
                      max_len=64, kv_dtype="int8", prefix_cache=False)
    try:
        return eng.submit(prompt, n_new).result(timeout=120)
    finally:
        eng.shutdown()


_BODIES = {
    "llama-bf16": (lambda m: m, _expected, {}),
    "llama-int8kv": (lambda m: m, _alone_int8, {"kv_dtype": "int8"}),
    "latent": (lambda m: _latent_model(), _latent_greedy, {}),
}
_ORACLE = {}


@pytest.mark.parametrize("route", [
    dict(decode_kernel="pallas", kernel_interpret=True),
    dict(decode_kernel="gather"),
], ids=["paged-interpret", "gather"])
@pytest.mark.parametrize("body", sorted(_BODIES))
def test_a_dead_row_stays_put_writes_nothing_and_costs_no_token(
        model, body, route):
    """Two requests beside two slots never used; A's budget ends inside
    its second chunk and B decodes four chunks longer.  Every body of
    `decode_chunk`, on both routes: (a) the tokens are the oracle's,
    (b) on the DEVICE a finished row's position stays at its stop while
    the chunks go on, and a slot never used stays at 0, (c) a request
    that shares A's first blocks through the prefix cache, admitted
    after A ran its dead steps with its real table, gets the oracle's
    tokens: a dead row wrote nothing into a shared block."""
    pick, oracle, kw = _BODIES[body]
    cfg, params = pick(model)
    rng = np.random.RandomState(5)
    a = [int(x) for x in rng.randint(1, cfg.vocab_size, size=20)]
    b = [int(x) for x in rng.randint(1, cfg.vocab_size, size=5)]
    c = a[:16] + [int(x) for x in rng.randint(1, cfg.vocab_size, size=4)]
    reqs = [(a, 6), (b, 23), (c, 5)]
    if body not in _ORACLE:
        _ORACLE[body] = [oracle(cfg, params, p, n) for p, n in reqs]
    want = _ORACLE[body]
    eng = LlamaEngine(cfg, params, slots=4, chunk=4, block_size=8,
                      max_len=64, prefix_cache=True, **kw, **route)
    try:
        assert not np.asarray(eng._stop).any()  # nothing admitted: all dead
        futs = [eng.submit(p, n) for p, n in reqs[:2]]
        assert [f.result(timeout=300) for f in futs] == want[:2]
        # A took 5 steps (2 chunks), B 22 (6 chunks): A's slot sat dead,
        # released and unreused, through B's last four
        ring = {r["tokens_in"]: r for r in eng.stats()["request_ring"]}
        assert ring[20]["harvests"] == 2 and ring[5]["harvests"] == 6
        assert sorted(np.asarray(eng._pos)) == [0, 0, 20 + 6 - 1, 5 + 23 - 1]
        assert sorted(np.asarray(eng._stop)) == sorted(np.asarray(eng._pos))
        # A's first two blocks, which the prefix cache now owns, hold
        # what A's prefill wrote, bit for bit, in every cache leaf
        shared, path = eng._radix.match(a)
        eng._radix.release(path)
        assert len(shared) == 2
        # (the engine's own program, as admission runs it: A from the
        # row's first token, its KV into blocks 1..3 of a zeroed cache;
        # whoever else shared A's row, masked, added exact zeros)
        N, K, i32 = eng._pack_sizes[-1], eng._pack_rows, np.int32
        tokens, seg, posn = np.zeros(N, i32), np.full(N, -1, i32), np.zeros(
            N, i32)
        tokens[:20], seg[:20], posn[:20] = a, 0, np.arange(20)
        blk = np.zeros(N // 8, i32)
        blk[:3] = [1, 2, 3]
        none = np.full(K, eng.slots, i32)  # no slot's state is set
        written = eng._prefill_packed_for(N)(
            params, *[jnp.zeros_like(x) for x in eng._cache], tokens, seg,
            posn, blk, np.full(K, 19, i32), none, none, none,
            eng._pos, eng._tok, eng._stop)[:len(eng._cache)]

        def prefix_blocks_intact():
            for leaf, ref in zip(eng._cache, written):
                np.testing.assert_array_equal(
                    np.asarray(leaf[:, np.asarray(shared)]),
                    np.asarray(ref[:, 1:3]))

        prefix_blocks_intact()
        assert eng.submit(*reqs[2]).result(timeout=300) == want[2]
        s = eng.stats()
        assert s["prefix_hit_tokens"] == 16
        prefix_blocks_intact()  # C's dead steps had them in its table too
        pos = sorted(np.asarray(eng._pos))
        assert pos[:2] == [0, 0] and 20 + 5 - 1 in pos
        live = sum(t["row_steps_live"] for t in s["tick_ring"])
        assert live == sum(n - 1 for _, n in reqs)
        assert live < sum(t["row_steps"] for t in s["tick_ring"])
    finally:
        eng.shutdown()
