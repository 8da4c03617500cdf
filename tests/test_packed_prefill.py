"""Admission's packed prefill (CPU, tiny engines): a tick's cache-miss
prompts lie end to end in ONE program (`prefill_packed_n<N>`), which
must leave the cache, the slots' device state and the served tokens
exactly where the one-prompt programs (`prefill_b*` + `kv_write_*`, the
path before it) leave them; admission's two phases (plan on the host,
then dispatch); the counters that say how often packing engages (RT008:
all prompt RNGs seeded).
"""

import dataclasses
import time
from concurrent.futures import Future

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import deepseek_v3, llama  # noqa: E402
from ray_tpu.serve import llm_engine  # noqa: E402
from ray_tpu.serve.llm_engine import LlamaEngine, _pack_sizes  # noqa: E402

SLOTS, MAX_LEN, BS, NEW = 16, 64, 8, 3


def _llama_greedy(cfg, params, prompt, n_new):
    out = llama.generate(cfg, params, jnp.asarray([prompt], jnp.int32), n_new)
    return [int(t) for t in np.asarray(out)[0]]


def _models():
    # float32 both, so that "the same values" can be held to 1e-5: what
    # is under test is which rows attend to which, where each is
    # rotated and which block it lands in
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=128),
                              dtype=jnp.float32)
    lcfg = deepseek_v3.DeepseekV3Config.tiny()
    return {
        "llama": (cfg, llama.init_params(cfg, jax.random.PRNGKey(0)),
                  llama.forward),
        "latent-moe": (lcfg, deepseek_v3.init_params(
            lcfg, jax.random.PRNGKey(0), std=0.2), deepseek_v3.forward),
    }


@pytest.fixture(scope="module")
def engines():
    """One engine a model for the whole module (its programs compile
    once), beside the model's dedicated forward, jitted."""
    made = {}
    for name, (cfg, params, forward) in _models().items():
        made[name] = (LlamaEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                                  chunk=2, block_size=BS, prefix_cache=False),
                      jax.jit(lambda t, f=forward, c=cfg, p=params: f(c, p, t)))
    yield made
    for eng, _ in made.values():
        eng.shutdown()


def _is_greedy(forward, prompt, got):
    """`got` is the greedy continuation of `prompt` under the dedicated
    forward: every token is the argmax given all before it."""
    lg = np.asarray(forward(jnp.asarray([prompt + got], jnp.int32)))[0]
    return np.argmax(lg[len(prompt) - 1:-1], axis=-1).tolist() == got


def _prompts(lengths, vocab, seed):
    rng = np.random.RandomState(seed)
    return [[int(x) for x in rng.randint(1, vocab, size=n)] for n in lengths]


def _queue_entry(prompt, n_new):
    return (list(prompt), n_new, Future(), time.time(), None, None)


def _burst(eng, reqs):
    """Every request into the queue at once, as one tick's admissions
    (submit() would wake the loop on the first of them)."""
    entries = [_queue_entry(p, n) for p, n in reqs]
    with eng._wake:
        eng._queue.extend(entries)
        eng._wake.notify()
    return [e[2] for e in entries]


# lengths a row cycles through; the cap is MAX_LEN tokens a program
LENGTHS = {
    "equal": (16,),
    "mixed-pad-tail": (5, 12, 17),
    "splits-at-the-cap": (30, 20, 27),
}


def _zero_device(eng):
    """An idle engine's cache and slot state, zeroed: what the packed
    programs then write is all there is to compare."""
    eng._cache = tuple(jnp.zeros_like(x) for x in eng._cache)
    eng._pos, eng._tok, eng._stop = (
        jnp.zeros_like(x) for x in (eng._pos, eng._tok, eng._stop))


def _per_request_reference(eng, plans):
    """The path before packing, on a zeroed cache: one `prefill_b*` and
    one `kv_write_*` a request, the first token picked from the logits'
    last real row.  Returns (cache, pos, tok, stop)."""
    i32 = jnp.int32
    cache = tuple(jnp.zeros_like(x) for x in eng._cache)
    state = tuple(jnp.zeros((eng.slots,), i32) for _ in range(3))
    for prompt, slot, own, stop in plans:
        T = len(prompt)
        bucket = min(llm_engine._next_pow2(T), eng.max_len - 1)
        logits, *kv = eng._prefill_for(bucket)(
            eng.params, jnp.asarray([prompt + [0] * (bucket - T)], i32))
        tok0 = jnp.argmax(logits[T - 1], axis=-1).astype(i32)
        nb = llm_engine._cdiv(T, eng.block_size)
        out = eng._write_blocks_for(bucket, nb)(
            *cache, *kv, jnp.asarray(own[:nb], i32), jnp.asarray(slot, i32),
            jnp.asarray(T, i32), tok0, state[0], state[1],
            jnp.asarray(stop, i32), state[2])
        cache, state = tuple(out[:-3]), tuple(out[-3:])
    return (cache,) + state


@pytest.mark.parametrize("kind", sorted(LENGTHS))
@pytest.mark.parametrize("rows", [1, 3, 16])
@pytest.mark.parametrize("model", ["llama", "latent-moe"])
def test_packed_prefill_equals_one_prefill_a_request(engines, model, rows,
                                                     kind):
    eng, forward = engines[model]
    lengths = [LENGTHS[kind][i % len(LENGTHS[kind])] for i in range(rows)]
    prompts = _prompts(lengths, eng.cfg.vocab_size, seed=rows)

    # (a) through the engine, admitted in one tick: the greedy tokens
    # of a dedicated forward, which are what the one-prompt path served
    futs = _burst(eng, [(p, NEW) for p in prompts])
    for p, f in zip(prompts, futs):
        got = f.result(timeout=300)
        assert len(got) == NEW and _is_greedy(forward, p, got)

    # (b) the programs themselves, on the idle engine (its loop sleeps
    # until a submit): plan, dispatch, and compare what is on the device
    bs = eng.block_size
    calls = eng._prefill_calls
    with eng._lock:
        plans = [eng._plan(p, NEW, Future(), time.time()) for p in prompts]
    kept = [(p.prompt, p.slot, list(p.own), p.req["stop"]) for p in plans]
    _zero_device(eng)
    eng._prefill(plans)
    try:
        # as few programs as hold the rows at MAX_LEN tokens each
        programs, used = 0, MAX_LEN + 1
        for n in lengths:
            need = -(-n // bs) * bs
            if used + need > MAX_LEN:
                programs, used = programs + 1, 0
            used += need
        assert eng._prefill_calls - calls == programs
        assert sorted(eng._active) == sorted(s for _, s, _, _ in kept)
        want_cache, want_pos, want_tok, want_stop = _per_request_reference(
            eng, kept)
        np.testing.assert_array_equal(np.asarray(eng._pos), want_pos)
        np.testing.assert_array_equal(np.asarray(eng._stop), want_stop)
        np.testing.assert_array_equal(np.asarray(eng._tok), want_tok)
        for prompt, slot, own, stop in kept:
            T = len(prompt)
            assert int(eng._pos[slot]) == T and int(eng._stop[slot]) == stop
            blocks = np.asarray(own[:-(-T // bs)])
            for got, want in zip(eng._cache, want_cache):
                def real(pool):  # the prompt's own rows, in order
                    x = np.asarray(pool[:, blocks])
                    return x.reshape((x.shape[0], -1) + x.shape[3:])[:, :T]
                np.testing.assert_allclose(real(got), real(want),
                                           rtol=1e-5, atol=1e-5)
    finally:
        # hand the slots and blocks back; nothing on the device is owed
        with eng._lock:
            for slot in list(eng._active):
                eng._release(slot, eng._active.pop(slot))
        _zero_device(eng)


def test_the_pack_sizes_are_a_small_closed_set_in_whole_blocks():
    # the benchmark's engines: max_len 1296 and 2320 at 16-token blocks
    assert _pack_sizes(1296, 16) == [128, 256, 512, 1024, 1296]
    assert _pack_sizes(2320, 16) == [128, 256, 512, 1024, 2048, 2320]
    assert _pack_sizes(48, 8) == [48]  # under the floor: one program
    sizes = _pack_sizes(1200, 48)
    assert sizes[-1] == 1200 and all(n % 48 == 0 for n in sizes)
    assert sizes == sorted(set(sizes))
    # padding: the smallest size that holds `used` tokens wastes under
    # half of itself
    for used in range(128, 1297, 16):
        n = next(n for n in _pack_sizes(1296, 16) if n >= used)
        assert (n - used) / n < 1 / 2


@pytest.fixture
def small(engines):
    cfg, params = engines["llama"][0].cfg, engines["llama"][0].params

    def make(**kw):
        eng = LlamaEngine(cfg, params, chunk=2, block_size=BS, **kw)
        made.append(eng)
        return eng

    made = []
    yield make
    for eng in made:
        eng.shutdown()


def _drive(eng, admissions):
    """One tick by hand, on an engine whose loop sleeps (nothing was
    submitted): what `_loop` does between its pop and its next wait."""
    time.sleep(0.1)  # a tick that answered the last request has ended
    eng._pending_admissions = len(admissions)
    eng._tick(admissions, time.time())
    return admissions


def test_a_pool_that_runs_out_mid_plan_requeues_in_arrival_order(small):
    """Room for two of four: the third stops the plan and goes back to
    the queue's head with the fourth behind it (which alone would have
    fitted: no reordering); the two before are dispatched, in one
    program."""
    eng = small(slots=4, max_len=MAX_LEN, kv_blocks=8, prefix_cache=False)
    cfg, params = eng.cfg, eng.params
    prompts = _prompts((20, 20, 20, 3), cfg.vocab_size, seed=7)
    # 20 + 10 - 1 = 29 positions: 4 blocks each; the last needs 1
    entries = [_queue_entry(p, 10) for p in prompts[:3]] + [
        _queue_entry(prompts[3], 2)]
    admissions = _drive(eng, list(entries))
    assert admissions == entries[:2]
    assert list(eng._queue) == entries[2:]
    assert eng._pending_admissions == 0 and len(eng._active) == 2
    s = eng.stats()
    assert (s["prefill_calls"], s["prefill_rows"]) == (1, 2)
    assert s["blocks_free"] == 0 and s["queued"] == 2
    # the loop takes it from here: everyone is answered, in order
    with eng._wake:
        eng._wake.notify()
    for p, n, fut, *_ in entries:
        assert fut.result(timeout=300) == _llama_greedy(cfg, params, p, n)
    waits = sorted((r["queue_s"], r["tokens_in"], r["tokens_out"])
                   for r in eng.stats()["request_ring"])
    # the two that were requeued waited for the first two's blocks
    assert [w[1:] for w in waits[2:]] in ([(20, 10), (3, 2)],
                                          [(3, 2), (20, 10)])


def test_a_prefix_hit_and_two_misses_in_one_tick_take_their_two_routes(
        small):
    """Misses are packed into one program; a hit prefills its suffix
    alone, behind the program that wrote the blocks it shares, be they
    an earlier request's or a miss's of the SAME tick."""
    eng = small(slots=8, max_len=MAX_LEN)
    cfg, params = eng.cfg, eng.params
    a, m1, m2 = _prompts((20, 21, 9), cfg.vocab_size, seed=11)
    tail = _prompts((4, 5), cfg.vocab_size, seed=12)
    assert eng.submit(a, 4).result(timeout=300) == _llama_greedy(
        cfg, params, a, 4)  # a's first two blocks are cached now
    hit_old = a[:16] + tail[0]       # shares a's blocks
    hit_new = m1[:16] + tail[1]      # shares m1's, written in this tick
    routes = []
    for name in ("_run_packed", "_run_suffix"):
        def spy(*args, _name=name, _fn=getattr(eng, name)):
            routes.append((_name, [len(p.prompt) for p in args[-1]]
                           if _name == "_run_packed"
                           else len(args[0].prompt)))
            return _fn(*args)
        setattr(eng, name, spy)
    base = eng.stats()
    entries = [_queue_entry(p, 5) for p in (m1, hit_old, m2, hit_new)]
    _drive(eng, list(entries))
    assert routes == [("_run_packed", [21, 9]), ("_run_suffix", 20),
                      ("_run_suffix", 21)]
    s = eng.stats()
    assert s["prefill_calls"] - base["prefill_calls"] == 3
    assert s["prefill_rows"] - base["prefill_rows"] == 4
    assert s["prefix_hit_tokens"] - base["prefix_hit_tokens"] == 32
    assert s["prefill_tokens"] - base["prefill_tokens"] == 21 + 9 + 4 + 5
    with eng._wake:
        eng._wake.notify()
    for p, n, fut, *_ in entries:
        assert fut.result(timeout=300) == _llama_greedy(cfg, params, p, n)


def test_rows_per_program_is_above_one_after_a_burst_and_one_alone(small):
    eng = small(slots=8, max_len=304, prefix_cache=False)
    assert eng._pack_sizes == [128, 256, 304]
    cfg, params = eng.cfg, eng.params
    lone = _prompts((12, 40, 7), cfg.vocab_size, seed=3)
    for p in lone:
        eng.submit(p, 2).result(timeout=300)
    s = eng.stats()
    assert s["prefill_rows"] == s["prefill_calls"] == 3
    assert s["prefill_padded_tokens"] == 3 * 128
    assert s["prefill_tokens"] == 12 + 40 + 7
    # five at once: 16 + 40 + 8 + 136 + 64 = 264 tokens in whole blocks
    burst = _prompts((12, 40, 7, 130, 64), cfg.vocab_size, seed=4)
    for p, f in zip(burst, _burst(eng, [(p, 4) for p in burst])):
        assert f.result(timeout=300) == _llama_greedy(cfg, params, p, 4)
    s2 = eng.stats()
    assert s2["prefill_calls"] - s["prefill_calls"] == 1
    assert s2["prefill_rows"] - s["prefill_rows"] == 5
    assert s2["prefill_padded_tokens"] - s["prefill_padded_tokens"] == 304
    assert s2["prefill_tokens"] - s["prefill_tokens"] == 253
    assert s2["prefill_rows"] / s2["prefill_calls"] == 2.0
    # and each request's record says how many its program held
    assert [r["prefill_rows"] for r in s2["request_ring"]] == [1] * 3 + [5] * 5


@pytest.mark.parametrize("name", ["engine_prefill_rows_per_program",
                                  "engine_prefill_rows_per_program.chat"])
def test_the_reader_counts_the_windows_programs_not_the_engines_life(name):
    """The benchmark's reader: over the window's ok records a program
    of r rows is r records of 1/r each, so warm-up's lone requests
    (before the window) do not pull the ratio to 1; None where the
    records keep no `prefill_rows` (the parent)."""
    from benchmarks import manifest

    def ctx(ring, answers):
        return {"plane": "serve", "client": {"per_replica": {"0": answers}},
                "replicas": [{"rid": "0", "engine": {"request_ring": ring}}]}

    def rec(rows, status="ok"):
        return {"status": status, "prefill_rows": rows}

    read = manifest.layer_metric(name).read
    ring = ([rec(1)] * 3 + [rec(4)] * 4 + [rec(2)] * 2 + [rec(1)]
            + [rec(None, "shed_expired")])
    # the window's 8: seven ok records of three programs (4, 2, 1 rows)
    assert read(ctx(ring, 8)) == pytest.approx(7 / 3)
    assert read(ctx([rec(1)] * 4, 4)) == 1.0
    assert read(ctx([{"status": "ok"}] * 4, 4)) is None
    assert read(ctx([], 0)) is None


def test_the_warm_up_compiles_and_runs_the_whole_closed_set(small):
    """What the kernel route does before it takes requests (on the chip;
    here by hand): every size compiled and run once on padding alone,
    so that no admission ever compiles."""
    eng = small(slots=4, max_len=304, prefix_cache=False)
    eng._warm_kernel_route()
    assert sorted(eng._packed_cache) == eng._pack_sizes == [128, 256, 304]
    compiled = {n: fn._cache_size() for n, fn in eng._packed_cache.items()}
    assert compiled == {128: 1, 256: 1, 304: 1}
    s = eng.stats()
    assert s["prefill_calls"] == s["prefill_rows"] == 0
    assert not np.asarray(eng._stop).any() and not np.asarray(eng._pos).any()
    for leaf in eng._cache:  # only the scratch block was written
        assert not np.asarray(leaf[:, 1:]).any()
    p = _prompts((150,), eng.cfg.vocab_size, seed=5)[0]
    assert eng.submit(p, 3).result(timeout=300) == _llama_greedy(
        eng.cfg, eng.params, p, 3)
    assert compiled == {  # the ones compiled at start, and no other
        n: fn._cache_size() for n, fn in eng._packed_cache.items()}


def test_one_prompt_a_program_where_attention_is_not_dense(engines):
    """The segment mask needs the dense form, so another attention
    keeps one prompt a program (the plain causal form, right-padded):
    the same program family, `K` = 1."""
    cfg = dataclasses.replace(engines["llama"][0].cfg, attention="ring")
    params = engines["llama"][0].params
    eng = LlamaEngine(cfg, params, slots=4, max_len=MAX_LEN, chunk=2,
                      block_size=BS)
    try:
        assert eng._pack_rows == 1 and eng._radix is None
        prompts = _prompts((12, 30, 5), cfg.vocab_size, seed=9)
        for p, f in zip(prompts, _burst(eng, [(p, 4) for p in prompts])):
            assert f.result(timeout=300) == _llama_greedy(cfg, params, p, 4)
        s = eng.stats()
        assert s["prefill_calls"] == s["prefill_rows"] == 3
    finally:
        eng.shutdown()
