"""The selecting model's ONE admission family: a tick's admissions in
one program through the block table (`dots3.forward_with_prefix`,
`SparseLatentEngineModel.suffix_prefill_packed`, the engine's
`_prefill_behind`).  At tiny widths on the CPU: a pack against its
requests one by one (each a pack of one, which `test_dots3.py` holds to
the float32 reference), the walk over the live query blocks only, and
the engine: a tick's hits in one program, its counters per request, a
long prompt's chunks, and no compile once the closed set is warm."""

import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import dots3
from ray_tpu.serve.engine_model import (SparseLatentEngineModel,
                                        engine_model_for)
from ray_tpu.serve.llm_engine import LlamaEngine

CFG = dots3.Dots3Config.tiny()
BS = 8          # a cache block, and here a query block too
W = 12          # a sequence's row of the block table
NB = 96         # the pool's blocks, the scratch block one of them
SLOTS = 20
DOC = 32        # a resident document: four blocks


@pytest.fixture(scope="module")
def params():
    return dots3.init_params(CFG, jax.random.PRNGKey(7), std=0.2)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n).tolist()


EM = engine_model_for(CFG, kv_dtype="model", block_size=BS, chunk=1,
                      paged=False, interpret=False)
_PROGRAMS = {}


def program(N):
    if N not in _PROGRAMS:
        _PROGRAMS[N] = jax.jit(EM.suffix_prefill_packed(N))
    return _PROGRAMS[N]


class Req:
    """Tokens `lo ..` of a sequence behind `before` (the blocks of its
    positions 0..lo), into `own`; `slot` None: a chunk that is not its
    prompt's last."""

    def __init__(self, before, toks, own, slot):
        self.before, self.toks, self.own, self.slot = before, toks, own, slot
        self.lo = len(before) * BS


def run(params, cache, state, reqs, N):
    """`reqs` end to end in one program of `N` rows, as the engine's
    `_suffix_arrays` lays them out."""
    K, i32 = 16, np.int32
    toks, posn = np.zeros(N, i32), np.full(N, -1, i32)
    tables = np.zeros((N // BS, W), i32)
    last, pos0, stop0 = (np.zeros(K, i32) for _ in range(3))
    slots = np.full(K, SLOTS, i32)
    at = 0
    for i, r in enumerate(reqs):
        S, blocks = len(r.toks), r.before + r.own
        nq = -(-S // BS)
        toks[at:at + S] = r.toks
        posn[at:at + S] = np.arange(r.lo, r.lo + S)
        tables[at // BS:at // BS + nq, :len(blocks)] = blocks
        if r.slot is not None:
            last[i], slots[i] = at + S - 1, r.slot
            pos0[i], stop0[i] = r.lo + S, r.lo + S + 7
        at += nq * BS
    assert at <= N
    out = program(N)(params, *cache, *map(jnp.asarray, (
        toks, posn, tables, last, slots, pos0, stop0)), *state)
    return tuple(out[:3]), tuple(out[3:])


@pytest.fixture(scope="module")
def world(params):
    """Three documents and the first 32 tokens of two long prompts,
    resident in blocks 1..20 of a pool; each prefilled alone."""
    cache = tuple(jnp.zeros((leaf.layers, NB, BS) + leaf.tail, leaf.dtype)
                  for leaf in EM.cache_leaves)
    state = (jnp.zeros((SLOTS,), jnp.int32),) * 3
    docs = []
    for d in range(5):
        blocks = list(range(1 + 4 * d, 5 + 4 * d))
        toks = tokens(DOC, seed=100 + d)
        cache, _ = run(params, cache, state, [Req([], toks, blocks, None)],
                       DOC)
        docs.append((toks, blocks))
    return cache, state, docs


def requests(n, docs):
    """`n` requests of mixed lengths behind DIFFERENT documents; the
    second behind nothing, the third the last chunk of a long prompt,
    the fourth a chunk that is not the last."""
    lengths = (5, 13, 8, 16, 3, 11, 16, 6)
    out, free = [], 21
    for i in range(n):
        S = lengths[i % len(lengths)]
        own = list(range(free, free + -(-(S + 7) // BS)))
        free += len(own)
        before = docs[i % 3][1]
        if i == 1:
            before = []
        elif i in (2, 3):
            before = docs[i + 1][1]
        out.append(Req(before, tokens(S, seed=200 + i), own,
                       None if i == 3 else i))
    return out


@pytest.mark.parametrize("n,N", [(1, 16), (3, 64), (16, 256)])
def test_a_pack_equals_its_requests_one_by_one(params, world, n, N):
    """The same first tokens, the same slot state, the same rows in the
    requests' own blocks; no other block is written."""
    cache0, state0, docs = world
    reqs = requests(n, docs)
    packed, pstate = run(params, cache0, state0, reqs, N)
    alone, astate = cache0, state0
    for r in reqs:
        alone, astate = run(params, alone, astate, [r], 16)
    for got, want in zip(pstate, astate):     # pos, tok, stop
        assert np.array_equal(np.asarray(got), np.asarray(want))
    admitted = [r.slot for r in reqs if r.slot is not None]
    assert np.asarray(pstate[0])[admitted].tolist() == [
        r.lo + len(r.toks) for r in reqs if r.slot is not None]
    owned = sorted(b for r in reqs for b in r.own[:-(-len(r.toks) // BS)])
    others = sorted(set(range(NB)) - set(owned))
    for leaf0, leaf_p, leaf_a in zip(cache0, packed, alone):
        p, a = np.asarray(leaf_p), np.asarray(leaf_a)
        assert np.array_equal(p[:, others], np.asarray(leaf0)[:, others])
        for r in reqs:
            for j in range(len(r.toks)):
                blk, off = r.own[j // BS], j % BS
                assert np.abs(p[:, blk, off] - a[:, blk, off]).max() < 1e-4
        assert np.abs(p[:, owned]).max() > 0.1


def test_a_dead_query_block_runs_no_selection(params, world):
    """The layers walk the query blocks that hold a token and no other:
    the loop's trips, counted inside it."""
    cache0, _, docs = world
    fn = jax.jit(lambda p, t, q, c, tb: dots3.forward_with_prefix(
        CFG, p, t, q, c, tb)[2])
    N = 64
    for live in (0, 1, 3):
        posn = np.full(N, -1, np.int32)
        tables = np.zeros((N // BS, W), np.int32)
        for b in range(live):      # `live` hits of 5 tokens, a block each
            posn[b * BS:b * BS + 5] = np.arange(DOC, DOC + 5)
            tables[b, :5] = docs[b][1] + [40 + b]
        stats = fn(params, jnp.zeros(N, jnp.int32), jnp.asarray(posn),
                   cache0, jnp.asarray(tables))
        assert int(stats["query_blocks"]) == CFG.n_layers * live


# -- the engine ---------------------------------------------------------
def _burst(eng, reqs):
    """Every request into the queue at once, as one tick's admissions."""
    entries = [(list(p), n, Future(), time.time(), None, None)
               for p, n in reqs]
    with eng._wake:
        eng._queue.extend(entries)
        eng._wake.notify()
    return [e[2] for e in entries]


_FORWARD = jax.jit(lambda p, t: dots3.forward(CFG, p, t))


def _is_greedy(params, prompt, got, width=128):
    """`got` is the greedy continuation of `prompt` under the whole
    forward: every token the argmax given all before it."""
    seq = list(prompt) + list(got)
    lg = np.asarray(_FORWARD(params, jnp.asarray(
        seq + [0] * (width - len(seq)))))
    return np.argmax(lg[len(prompt) - 1:len(seq) - 1], -1).tolist() == got


@pytest.fixture
def engine(params, monkeypatch):
    """Requests aligned to ONE block of 8 in a program (the published
    64 would hold one request a program at these sizes)."""
    monkeypatch.setattr(SparseLatentEngineModel, "pack_align", BS)
    made = []

    def make(**kw):
        made.append(LlamaEngine(CFG, params, chunk=2, block_size=BS, **kw))
        return made[-1]

    yield make
    for eng in made:
        eng.shutdown()


def _counters(eng):
    s = eng.stats()
    return np.asarray([s[k] for k in (
        "prefill_calls", "prefill_rows", "prefix_hits", "prefix_hit_tokens",
        "prefill_tokens", "prefill_padded_tokens")])


def test_a_ticks_hits_share_one_program(engine, params):
    """Five hits queued before a tick: ONE program, every record's
    `prefill_rows` 5, the counters per request, and the answers those
    of the same engine asked one by one."""
    eng = engine(slots=6, max_len=96, kv_blocks=80, prefill_chunk=64)
    assert eng._pack_sizes == [64] and eng._pack_align == BS
    doc = tokens(DOC, seed=1)
    eng.submit(doc + tokens(4, seed=2), 2).result(timeout=600)
    questions = [tokens(n, seed=10 + n) for n in (5, 8, 3, 11, 6)]
    before = _counters(eng)
    futs = _burst(eng, [(doc + q, 6) for q in questions])
    outs = [f.result(timeout=600) for f in futs]
    real = sum(map(len, questions))
    assert (_counters(eng) - before).tolist() == [
        1, 5, 5, 5 * DOC, real, 64]
    ring = eng.stats()["request_ring"][-5:]
    assert [r["prefill_rows"] for r in ring] == [5] * 5
    assert [r["prefill_chunks"] for r in ring] == [1] * 5
    assert [r["tokens_hit"] for r in ring] == [DOC] * 5
    tick = next(t for t in reversed(eng.stats()["tick_ring"])
                if t.get("prefill_calls"))
    assert (tick["prefill_calls"], tick["prefill_rows"],
            tick["prefix_hit_tokens"]) == (1, 5, 5 * DOC)
    mid = _counters(eng)
    for q, out in zip(questions, outs):
        assert _is_greedy(params, doc + q, out)
        assert eng.submit(doc + q, 6).result(timeout=600) == out
    assert (_counters(eng) - mid)[:3].tolist() == [5, 5, 5]


def test_a_long_prompts_last_chunk_shares_a_program(engine, params):
    """A prompt of 80 tokens at a chunk of 64 and a short one behind
    nothing, in one tick: the first chunk fills a program alone, the
    rest of it and the short prompt share the second."""
    eng = engine(slots=4, max_len=96, kv_blocks=60, prefill_chunk=64)
    long_, short = tokens(80, seed=3), tokens(10, seed=4)
    before = _counters(eng)
    futs = _burst(eng, [(long_, 5), (short, 5)])
    outs = [f.result(timeout=600) for f in futs]
    assert (_counters(eng) - before).tolist() == [2, 2, 0, 0, 90, 128]
    ring = eng.stats()["request_ring"][-2:]
    by_len = {r["tokens_in"]: r for r in ring}
    assert (by_len[80]["prefill_chunks"], by_len[80]["prefill_rows"]) == (2, 2)
    assert (by_len[10]["prefill_chunks"], by_len[10]["prefill_rows"]) == (1, 2)
    assert _is_greedy(params, long_, outs[0])
    assert _is_greedy(params, short, outs[1])


def test_no_admission_compiles_once_the_closed_set_is_warm(engine, params):
    """What the kernel route does at start (on the chip; here by hand):
    every size of the ladder compiled and run on padding alone, which
    writes nothing.  Then a stream of hits that takes every size, alone
    and together, a miss and a long prompt: no program is added and
    none compiles again."""
    eng = engine(slots=12, max_len=320, kv_blocks=200, prefill_chunk=256)
    assert eng._pack_sizes == [128, 256]
    eng._warm_kernel_route()
    assert sorted(eng._packed_cache) == [128, 256]
    assert all(fn.__name__.startswith("suffix_prefill_packed_n")
               for fn in eng._packed_cache.values())
    assert eng.stats()["prefill_calls"] == 0
    for leaf in eng._cache:
        assert not np.asarray(leaf[:, 1:]).any()
    compiled = {n: fn._cache_size() for n, fn in eng._packed_cache.items()}
    assert compiled == {128: 1, 256: 1}
    doc = tokens(DOC, seed=5)
    eng.submit(doc + tokens(3, seed=6), 2).result(timeout=600)
    for n_hits in (1, 5, 12):       # 16, 80 and 192 rows: N 128, 128, 256
        futs = _burst(eng, [(doc + tokens(9 + i, seed=30 + i), 3)
                            for i in range(n_hits)])
        for f in futs:
            assert len(f.result(timeout=600)) == 3
    eng.submit(tokens(300, seed=7), 3).result(timeout=600)  # 256 + 44
    assert eng.stats()["prefill_padded_tokens"] == 128 * 3 + 256 * 2 + 128
    assert compiled == {n: fn._cache_size()
                        for n, fn in eng._packed_cache.items()}
    assert not eng._suffix_cache and not eng._write_cache
