"""Continuous-batching engine correctness (CPU, tiny model).

The invariant: greedy decoding is deterministic and rows are
independent, so every request served by the shared-slot engine must
produce EXACTLY the tokens a dedicated `llama.generate` yields for
the same prompt — across mixed lengths, mixed budgets, concurrent
submission, slot reuse, queueing beyond the slot count, paged KV
block reuse, radix prefix-cache hits, and LRU eviction under
block-pool pressure (RT008: all prompt RNGs seeded).
"""

import threading
import time
from concurrent.futures import Future

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


# ----------------------------------------------------------------------
# the hand-off: a row whose last wanted step lies in the chunk just
# dispatched gives its slot back at that dispatch, a chunk before the
# harvest that resolves it
# ----------------------------------------------------------------------
def _burst(eng, reqs, **fields):
    """Every request into the queue at once, as one tick's admissions."""
    entries = [(list(p), n, Future(), time.time(), None, None,
                *([fields] if fields else []))
               for p, n in reqs]
    with eng._wake:
        eng._queue.extend(entries)
        eng._wake.notify()
    return [e[2] for e in entries]


def _teacher_forced(logits_of, width):
    """An oracle from ONE whole-sequence forward at one padded length:
    `got` is the greedy continuation of `prompt` iff every token of it
    is the argmax given all before it."""
    def check(prompt, n, got):
        seq = list(prompt) + list(got)
        lg = np.asarray(logits_of(seq + [0] * (width - len(seq))))
        want = np.argmax(lg[len(prompt) - 1:len(seq) - 1], -1).tolist()
        assert len(got) == n and list(got) == want, (len(prompt), n)
    return check


def _rand_prompts(vocab, lengths, seed):
    rng = np.random.RandomState(seed)
    return [[int(x) for x in rng.randint(1, vocab, size=T)] for T in lengths]


# answers SHORTER than a chunk of 4 (1, 2, 3), answers whose last step
# ends a chunk (5, 9: 1 + whole chunks of steps) and answers that end
# inside one, dealt over more callers than the three slots
_BUDGETS = (7, 1, 5, 2, 9, 3, 11, 4, 6, 1, 8)


_GENERATED = {}


def _seam_llama(model, prefix_cache):
    """(bfloat16: a teacher-forced float pass is no oracle for it, a
    dedicated `llama.generate` is, bit for bit; once for both cases)"""
    cfg, params = model
    prompts = _prompts_with_shared_system_prompt(
        cfg, len(_BUDGETS), np.random.RandomState(11))

    def check(prompt, n, got):
        key = (tuple(prompt), n)
        if key not in _GENERATED:
            _GENERATED[key] = _expected(cfg, params, prompt, n)
        assert got == _GENERATED[key], (len(prompt), n)

    return (cfg, params, dict(max_len=64, prefix_cache=prefix_cache),
            prompts, check, {})


def _seam_state(_model):
    from ray_tpu.models import brumby

    cfg = brumby.BrumbyConfig.tiny()
    params = brumby.init_params(cfg, jax.random.PRNGKey(0), std=0.2)
    fwd = jax.jit(lambda t: brumby.forward(cfg, params, t[None], chunk=8)[0][0])
    prompts = _rand_prompts(cfg.vocab_size,
                            (5, 13, 8, 20, 3, 17, 9, 12, 6, 15, 10), 12)
    return (cfg, params, dict(max_len=48), prompts,
            _teacher_forced(lambda s: fwd(jnp.asarray(s)), 48), {})


def _seam_hybrid(_model):
    """Paged K and V beside a per-slot convolution state: a slot's next
    holder overwrites BOTH behind the old row's last chunk."""
    from ray_tpu.models import lfm2

    cfg = lfm2.Lfm2MoeConfig.tiny()
    params = lfm2.init_params(cfg, jax.random.PRNGKey(0), std=0.2)
    fwd = jax.jit(lambda t: lfm2.forward(cfg, params, t[None])[0][0])
    prompts = _rand_prompts(cfg.vocab_size,
                            (5, 13, 8, 20, 3, 17, 9, 12, 6, 15, 10), 17)
    return (cfg, params, dict(max_len=48, kv_blocks=12), prompts,
            _teacher_forced(lambda s: fwd(jnp.asarray(s)), 48), {})


def _seam_latent(_model):
    cfg, params = _latent_model()
    from ray_tpu.models import deepseek_v3

    fwd = jax.jit(lambda t: deepseek_v3.forward(cfg, params, t[None])[0])
    prompts = _prompts_with_shared_system_prompt(
        cfg, len(_BUDGETS), np.random.RandomState(18))
    return (cfg, params, dict(max_len=64), prompts,
            _teacher_forced(lambda s: fwd(jnp.asarray(s)), 64), {})


def _seam_ring(_model):
    import test_mimo_v2 as tm

    params = tm.mimo_v2.init_params(tm.CFG, jax.random.PRNGKey(0), std=0.2)
    # long prompts admitted chunk by chunk, the slot's ring carried
    prompts = _rand_prompts(tm.CFG.vocab_size,
                            (5, 40, 12, 33, 16, 7, 64, 9, 21, 4, 18), 13)
    return (tm.CFG, params,
            dict(max_len=96, kv_blocks=30, prefill_chunk=16), prompts,
            _teacher_forced(
                lambda s: tm.forward_logits(tm.CFG, params, s), 96), {})


def _seam_hits(_model, monkeypatch):
    """The sparse-latent model behind ONE resident document: every
    request of the wave is a prefix HIT, packed with its tick's."""
    from ray_tpu.models import dots3
    from ray_tpu.serve.engine_model import SparseLatentEngineModel

    monkeypatch.setattr(SparseLatentEngineModel, "pack_align", 8)
    cfg = dots3.Dots3Config.tiny()
    params = dots3.init_params(cfg, jax.random.PRNGKey(7), std=0.2)
    fwd = jax.jit(lambda t: dots3.forward(cfg, params, t))
    doc, = _rand_prompts(cfg.vocab_size, (32,), 14)
    tails = _rand_prompts(cfg.vocab_size,
                          (5, 8, 3, 11, 6, 4, 9, 7, 2, 10, 12), 15)
    return (cfg, params,
            dict(max_len=96, kv_blocks=40, prefill_chunk=64),
            [doc + t for t in tails],
            _teacher_forced(lambda s: fwd(jnp.asarray(s)), 96),
            {"resident": doc})


def _seam_block_diffusion(_model):
    """Where the DEVICE counts what a chunk produced the host cannot
    know a row's last step at dispatch: nothing is handed off."""
    from benchmarks.reference import sdar as ref
    from test_sdar_model import model as sdar_model, reference_logits

    cfg, params = sdar_model(4)
    logits_of = reference_logits(cfg, params)
    prompts = _rand_prompts(cfg.vocab_size,
                            (8, 6, 13, 16, 5, 9, 11, 4, 7, 12, 10), 16)

    def check(prompt, n, got):
        want = ref.generate(prompt, n, cfg.block_length, 2, 0.9,
                            cfg.mask_id, logits_of)
        assert list(got) == want[0] and got.decided_at == want[1]

    return (cfg, params, dict(max_len=64, block_size=8, kv_blocks=28),
            prompts, check, {"fields": {"denoising_steps": 2},
                             "hands_off": False})


_SEAMS = {
    "llama-prefix-cache": lambda m, mp: _seam_llama(m, True),
    "llama-no-prefix-cache": lambda m, mp: _seam_llama(m, False),
    "latent-prefix-cache": lambda m, mp: _seam_latent(m),
    "state": lambda m, mp: _seam_state(m),
    "hybrid": lambda m, mp: _seam_hybrid(m),
    "window-ring": lambda m, mp: _seam_ring(m),
    "sparse-latent-every-request-a-hit": _seam_hits,
    "block-diffusion": lambda m, mp: _seam_block_diffusion(m),
}


def _pinned(radix):
    stack, pinned = list(radix._root.children.values()), []
    while stack:
        node = stack.pop()
        stack.extend(node.children.values())
        if node.refs:
            pinned.append(node.block)
    return pinned


@pytest.mark.parametrize("seam", list(_SEAMS))
def test_a_finished_row_hands_its_slot_over_at_dispatch(model, seam,
                                                        monkeypatch):
    """Eleven callers on three slots, answers shorter than a chunk, on a
    chunk's boundary and inside one, behind every seam: the tokens are
    the per-request oracle's, every finished request was handed off
    (none where the device counts), and an idle engine holds nothing:
    every block free or cached, no node of the trie pinned."""
    cfg, params, kw, prompts, check, more = _SEAMS[seam](model, monkeypatch)
    eng = LlamaEngine(cfg, params, slots=3, chunk=4,
                      **{"block_size": 8, **kw})
    try:
        before = 0
        if "resident" in more:  # the document, cached by a first request
            eng.submit(more["resident"] + [1, 2], 2).result(timeout=600)
            before = 1
        reqs = list(zip(prompts, _BUDGETS))
        fields = more.get("fields", {})
        fields = fields and eng._model.request_fields(**fields)
        # two waves: a burst that one tick admits from, then callers one
        # by one while the first wave's rows drain
        futs = _burst(eng, reqs[:6], **fields)
        futs += [eng.submit(p, n, **more.get("fields", {}))
                 for p, n in reqs[6:]]
        for (p, n), f in zip(reqs, futs):
            check(p, n, f.result(timeout=600))
        s = eng.stats()
        done = before + len(reqs)
        assert s["finished_total"] == done
        assert s["handoffs_total"] == (done if more.get("hands_off", True)
                                       else 0)
        if "resident" in more:
            assert s["prefix_hits"] == len(reqs)
        assert (s["active"], s["queued"], s["free_slots"]) == (0, 0, 3)
        assert not eng._handed and not eng._active
        assert s["blocks_free"] + s["blocks_cached"] == s["blocks_total"]
        assert eng._radix is None or not _pinned(eng._radix)
        ticks = s["tick_ring"]
        if more.get("hands_off", True) and len(ticks) < 32:
            assert sum(t["handed_off"] for t in ticks) == done
    finally:
        eng.shutdown()


class _Gate:
    """The engine thread's reads of a chunk's tokens, held at a gate:
    while it is closed the tick that would harvest waits INSIDE its
    read, after its own dispatch, with whatever that dispatch handed
    off still in `_handed`."""

    def __init__(self, monkeypatch):
        self.open, self.fail = threading.Event(), None
        real = np.asarray

        def read(a, *args, **kw):
            if (threading.current_thread().name == "llm-engine"
                    and isinstance(a, jax.Array)):
                assert self.open.wait(timeout=60)
                if self.fail is not None:
                    raise self.fail
            return real(a, *args, **kw)

        monkeypatch.setattr(np, "asarray", read)

    def handed_off(self, eng, n=1):
        """Waits until `n` requests drain behind the closed gate."""
        t_end = time.time() + 60
        while len(eng._handed) < n and time.time() < t_end:
            time.sleep(0.005)
        assert len(eng._handed) == n


@pytest.fixture
def gate(monkeypatch):
    g = _Gate(monkeypatch)
    yield g
    g.open.set()


@pytest.mark.parametrize("sweep", ["shutdown", "failed-tick", "begin-drain"])
def test_a_request_that_drains_is_never_left_pending(model, gate, sweep):
    """A request of 4 tokens at a chunk of 2 is handed off at its second
    dispatch; the tick's read (of its FIRST chunk) waits at the gate, so
    the sweep finds it in `_handed`: a shutdown cancels it at the loop's
    next turn, a tick that raises fails it and rebuilds the engine's
    state, a drain lets it run to its tokens."""
    from ray_tpu.exceptions import BackPressureError

    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=2, max_len=48, chunk=2,
                      block_size=8)
    try:
        prompt, = _rand_prompts(cfg.vocab_size, (12,), 21)
        fut = eng.submit(prompt, 4)
        gate.handed_off(eng)
        assert not fut.done() and not eng._active
        s = eng.stats()
        assert (s["active"], s["free_slots"], s["queue_depth"]) == (1, 2, 1)
        if sweep == "shutdown":
            threading.Timer(0.2, gate.open.set).start()
            eng.shutdown()  # the loop ends after the tick at the gate
            assert fut.cancelled()
        elif sweep == "failed-tick":
            gate.fail = RuntimeError("the read failed")
            gate.open.set()
            with pytest.raises(RuntimeError, match="the read failed"):
                fut.result(timeout=60)
            gate.fail = None
            assert eng.stats()["request_ring"][-1]["status"] == "error"
            # host and device state restart from scratch, and serve
            assert eng.submit(prompt, 4).result(timeout=120) == _expected(
                cfg, params, prompt, 4)
            s = eng.stats()
            assert (s["active"], s["free_slots"]) == (0, 2)
            assert s["blocks_free"] + s["blocks_cached"] == s["blocks_total"]
        else:
            eng.begin_drain()
            gate.open.set()
            assert fut.result(timeout=120) == _expected(
                cfg, params, prompt, 4)
            with pytest.raises(BackPressureError):
                eng.submit(prompt, 4).result(timeout=60)
        assert not eng._handed and not eng._active
    finally:
        gate.open.set()
        eng.shutdown()


def test_a_pool_that_runs_out_while_a_request_drains_requeues_in_order(
        model):
    """Seven blocks for requests of 3, 4, 3 and 4: two are admitted, the
    third waits for blocks.  The hand-off of the first (two chunks; the
    second runs four) frees three: the next tick admits the third and
    requeues the fourth, while the first still drains, and admission
    stays in arrival order."""
    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=3, max_len=48, chunk=4,
                      block_size=8, kv_blocks=7, prefix_cache=False)
    plans, plan = [], eng._plan

    def spy(prompt, *a, **k):
        out = plan(prompt, *a, **k)
        plans.append((len(prompt), out is not None, len(eng._handed)))
        return out

    eng._plan = spy
    try:
        prompts = _rand_prompts(cfg.vocab_size, (17, 18, 19, 20), 22)
        reqs = list(zip(prompts, (6, 14, 6, 6)))
        futs = _burst(eng, reqs)
        for (p, n), f in zip(reqs, futs):
            assert f.result(timeout=120) == _expected(cfg, params, p, n)
        admitted = [T for T, ok, _ in plans if ok]
        assert admitted == [17, 18, 19, 20]
        # refused for want of blocks while a handed-off request drained
        assert any(not ok and draining for _, ok, draining in plans)
        s = eng.stats()
        assert s["handoffs_total"] == s["finished_total"] == 4
        assert s["blocks_free"] == s["blocks_total"] == 7
    finally:
        eng.shutdown()
