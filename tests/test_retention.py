"""Power retention: the monomials, the recurrence against the quadratic
form, the chunked scan over a packed row, the decode step's dead rows,
the kernels in the Pallas interpreter against their plain-XLA bodies,
and the state model through the serve engine (a per-slot cache: no
blocks, no tables, no prefix cache)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.exceptions import PrefixCacheUnsupportedError
from ray_tpu.models import brumby
from ray_tpu.models.llama import Packed
from ray_tpu.ops import retention as R
from ray_tpu.serve.llm_engine import LlamaEngine

D, KV, G, L, SLOTS, C = 16, 2, 3, 2, 4, 8
H = KV * G
EPS = 1e-6
ROUTES = [{}, {"interpret": True}]
IDS = ["xla", "pallas-interpret"]


@pytest.fixture(autouse=True)
def _full_float32():
    # the CPU's default matmul keeps float32, the interpreter's `dot`
    # asks for the default: pin both so that tolerances mean one thing
    with jax.default_matmul_precision("highest"):
        yield


def _rand(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _gates(seed, *shape):
    # sigmoid ~0.9: a state that visibly decays over a dozen tokens
    return jax.nn.log_sigmoid(_rand(seed, *shape) + 2.0)


def _packed_row():
    """Two prompts of 13 and 20 tokens from chunk boundaries in a row of
    48: 0..12 (pad to 16), 16..35 (pad to 40), a chunk of padding."""
    T1, T2, N = 13, 20, 48
    seg, posn = np.full(N, -1, np.int32), np.zeros(N, np.int32)
    seg[:T1], posn[:T1] = 0, np.arange(T1)
    seg[16:16 + T2], posn[16:16 + T2] = 1, np.arange(T2)
    return N, [(0, T1, 2), (16, T2, 0)], jnp.asarray(seg), jnp.asarray(posn)


def _leaves(fill=0.0):
    return [jnp.full(s, fill, jnp.float32)
            for s in R.state_shapes(L, SLOTS, KV, D)]


def test_the_monomials_square_the_inner_product():
    """`phi(a) . phi(b) = (a . b)^2`, 8,320 values for 8,256 distinct
    monomials at d 128.  A float32 sum of d^2/2 products that cancel:
    1e-6 of `|a|^2 |b|^2`, what the terms are the size of."""
    a, b = _rand(0, 7, 128), _rand(1, 7, 128)
    got = jnp.sum(R.phi(a) * R.phi(b), axis=(-1, -2))
    size = float(jnp.max(jnp.sum(a * a, -1) * jnp.sum(b * b, -1)))
    np.testing.assert_allclose(got, jnp.sum(a * b, -1) ** 2, rtol=0,
                               atol=1e-6 * size)
    assert R.phi(a).shape == (7, 65, 128) and R.shifts(128) * 128 == 8320
    assert R.monomials(128) == 8256


@pytest.mark.parametrize("route", ROUTES, ids=IDS)
def test_recurrence_equals_the_quadratic_form(route):
    """The decode form from an empty state, one token at a time, is the
    same function as the quadratic form over the whole sequence.  2e-5
    relative to the largest output: float32 on both sides, the orders
    of summation differ (a state of 144 monomials against 12 scores).
    q and k share an offset so that no `(q . k)^2` is the size of its
    own rounding error: where the denominator vanishes the FUNCTION is
    ill-conditioned, in either form."""
    T = 12
    q, k, v = (_rand(2, T, H, D) + 1.0, _rand(3, T, KV, D) + 1.0,
               _rand(4, T, KV, D))
    g = _gates(5, T, KV)
    want = np.asarray(R.retention_quadratic(q, k, v, g, EPS))
    state, keysum = [jnp.zeros(s, jnp.float32)
                     for s in R.state_shapes(1, 1, KV, D)]
    live = jnp.ones((1,), bool)
    for t in range(T):
        o, state, keysum = R.retention_decode(
            q[t][None], k[t][None], v[t][None], g[t][None], state, keysum,
            live, 0, eps=EPS, **route)
        np.testing.assert_allclose(np.asarray(o[0]), want[t], rtol=0,
                                   atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("route", ROUTES, ids=IDS)
def test_chunked_scan_over_a_packed_row(route):
    """Every prompt of a packed row reads as the quadratic form of that
    prompt ALONE (a prompt's first chunk carries nothing in), its state
    at its last real token lands in its slot, right-padding and a chunk
    of padding change nothing, and the slots and layers no prompt names
    keep their bytes.  2e-5 of the largest output, as above."""
    N, prompts, seg, posn = _packed_row()
    q, k, v = _rand(6, N, H, D), _rand(7, N, KV, D), _rand(8, N, KV, D)
    g = _gates(9, N, KV)
    slots = jnp.asarray([2, 0, SLOTS, SLOTS], jnp.int32)
    o, state, keysum = R.retention_prefill(
        q, k, v, g, seg, posn, slots, *_leaves(7.0), 1, chunk=C, eps=EPS,
        **route)
    for lo, T, slot in prompts:
        sl = slice(lo, lo + T)
        want = np.asarray(R.retention_quadratic(q[sl], k[sl], v[sl], g[sl],
                                                EPS))
        np.testing.assert_allclose(np.asarray(o[sl]), want, rtol=0,
                                   atol=2e-5 * np.abs(want).max())
        # the slot's state is the recurrence's after the last real token
        st, zs = [jnp.zeros(s, jnp.float32)
                  for s in R.state_shapes(1, 1, KV, D)]
        for t in range(lo, lo + T):
            _, st, zs = R.retention_decode(
                q[t][None], k[t][None], v[t][None], g[t][None], st, zs,
                jnp.ones((1,), bool), 0, eps=EPS)
        np.testing.assert_allclose(np.asarray(state[1, slot]),
                                   np.asarray(st[0, 0]), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(keysum[1, slot]),
                                   np.asarray(zs[0, 0]), rtol=1e-4, atol=1e-4)
    for untouched in (state[0], state[1, 1], state[1, 3], keysum[0],
                      keysum[1, 1]):
        assert float(jnp.abs(untouched - 7.0).max()) == 0.0


def test_kernels_equal_their_xla_bodies():
    """The Pallas kernels (interpreter) against the plain-XLA bodies on
    the same inputs: the same algorithm tile for tile, so 1e-5."""
    N, _, seg, posn = _packed_row()
    q, k, v = _rand(6, N, H, D), _rand(7, N, KV, D), _rand(8, N, KV, D)
    g = _gates(9, N, KV)
    slots = jnp.asarray([2, 0, SLOTS, SLOTS], jnp.int32)
    outs = [R.retention_prefill(q, k, v, g, seg, posn, slots, *_leaves(),
                                1, chunk=C, eps=EPS, **route)
            for route in ROUTES]
    real = np.asarray(seg) >= 0
    np.testing.assert_allclose(np.asarray(outs[0][0])[real],
                               np.asarray(outs[1][0])[real], atol=1e-5)
    for a, b in zip(outs[0][1:], outs[1][1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    live = jnp.asarray([True, False, True, False])
    steps = [R.retention_decode(
        _rand(10, SLOTS, H, D), _rand(11, SLOTS, KV, D),
        _rand(12, SLOTS, KV, D), _gates(13, SLOTS, KV), *outs[0][1:], live,
        1, eps=EPS, **route) for route in ROUTES]
    for a, b in zip(steps[0], steps[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("route", ROUTES, ids=IDS)
def test_dead_rows_leave_their_state_untouched(route):
    """A row that owes no token is neither read nor written: its state
    and key sum keep their BYTES (garbage included), its output is
    zeros; with no row live nothing changes at all."""
    state, keysum = _leaves(3.0)
    state = state.at[1, 1].set(jnp.nan)     # a dead slot's garbage
    live = jnp.asarray([True, False, True, False])
    args = (_rand(10, SLOTS, H, D), _rand(11, SLOTS, KV, D),
            _rand(12, SLOTS, KV, D), _gates(13, SLOTS, KV))
    o, s2, z2 = R.retention_decode(*args, state, keysum, live, 1, eps=EPS,
                                   **route)
    for dead in (1, 3):
        np.testing.assert_array_equal(np.asarray(s2[:, dead]),
                                      np.asarray(state[:, dead]))
        np.testing.assert_array_equal(np.asarray(z2[:, dead]),
                                      np.asarray(keysum[:, dead]))
        assert float(jnp.abs(o[dead]).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(s2[0]), np.asarray(state[0]))
    assert float(jnp.abs(s2[1, 0] - 3.0).max()) > 0 and bool(
        jnp.isfinite(o[0]).all())
    _, s3, z3 = R.retention_decode(*args, state, keysum,
                                   jnp.zeros((SLOTS,), bool), 1, eps=EPS,
                                   **route)
    np.testing.assert_array_equal(np.asarray(s3), np.asarray(state))
    np.testing.assert_array_equal(np.asarray(z3), np.asarray(keysum))


# ----------------------------------------------------------------------
# a chunk whose state is written once: reads, then the flush
# ----------------------------------------------------------------------
def _warm_leaves(steps=3):
    """Leaves a few single steps of every row have filled (layer 1),
    so that a read-out has a denominator; layer 0 keeps its 7s."""
    state, keysum = _leaves(7.0)
    state, keysum = state.at[1].set(0.0), keysum.at[1].set(0.0)
    for t in range(steps):
        _, state, keysum = R.retention_decode(
            *_step_args(100 + 10 * t), state, keysum, jnp.ones((SLOTS,), bool),
            1, eps=EPS)
    return state, keysum


def _step_args(seed):
    # q and k share an offset, as in the recurrence's test
    return (_rand(seed, SLOTS, H, D) + 1.0, _rand(seed + 1, SLOTS, KV, D) + 1.0,
            _rand(seed + 2, SLOTS, KV, D), _gates(seed + 3, SLOTS, KV))


def _empty(held):
    k, v, G = R.pending_shapes(1, SLOTS, KV, D, held)
    return R.Pending(jnp.zeros(k[1:]), jnp.zeros(v[1:]), jnp.zeros(G[1:]),
                     jnp.zeros((), jnp.int32))


@pytest.mark.parametrize("chunk", [2, 8])
@pytest.mark.parametrize("route", ROUTES, ids=IDS)
def test_a_deferred_chunk_equals_single_steps(route, chunk):
    """`chunk - 1` reads and the flush against `chunk` single steps:
    every live `o_j`, and the state and key sum after the flush, at the
    float32 tolerance of `test_kernels_equal_their_xla_bodies` (1e-5,
    of outputs and states of order 1: the same function, the decays
    multiplied in another order).  Row 1 DIES inside the chunk and row
    2 is dead throughout: neither is flushed, both keep their BYTES
    (the state of a row that died is never read again); a read step
    has no state among its results, so every read sees the leaves the
    chunk found."""
    state0, keysum0 = _warm_leaves()
    dies = (chunk - 1) // 2                 # row 1's last live step
    lives = [jnp.asarray([True, j <= dies, False, True])
             for j in range(chunk)]
    args = [_step_args(200 + 10 * j) for j in range(chunk)]
    want, (st, zs) = [], (state0, keysum0)
    for a, live in zip(args, lives):
        o, st, zs = R.retention_decode(*a, st, zs, live, 1, eps=EPS)
        want.append(np.asarray(o))
    pend = _empty(chunk - 1)
    for j in range(chunk - 1):
        o, pend = R.retention_read(*args[j], state0, keysum0, pend, lives[j],
                                   1, eps=EPS, **route)
        np.testing.assert_allclose(np.asarray(o), want[j], atol=1e-5)
        assert int(pend.n) == j + 1
    o, state, keysum = R.retention_decode(
        *args[-1], state0, keysum0, lives[-1], 1, eps=EPS, pending=pend,
        **route)
    np.testing.assert_allclose(np.asarray(o), want[-1], atol=1e-5)
    assert float(np.abs(want[-1][0]).max()) > 0.01
    for row in (0, 3):
        np.testing.assert_allclose(np.asarray(state[1, row]),
                                   np.asarray(st[1, row]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(keysum[1, row]),
                                   np.asarray(zs[1, row]), atol=1e-5)
    for mine, before in ((state, state0), (keysum, keysum0)):
        for dead in (1, 2):
            np.testing.assert_array_equal(np.asarray(mine[:, dead]),
                                          np.asarray(before[:, dead]))
        np.testing.assert_array_equal(np.asarray(mine[0]),
                                      np.asarray(before[0]))
    assert float(jnp.abs(o[1]).max()) == 0.0 == float(jnp.abs(o[2]).max())


@pytest.mark.parametrize("route", ROUTES, ids=IDS)
def test_a_flush_with_nothing_held_is_the_single_step(route):
    """The flush whose `pending` holds nothing (whatever lies in its
    places) is the single step: a place that is not held adds exact
    zeros, BIT FOR BIT in plain XLA.  With no `pending` the kernel IS
    the single step's, operand for operand.  And a read's first result
    is not the shape the benchmark tells the flush by (the
    numerators)."""
    state0, keysum0 = _warm_leaves()
    live = jnp.asarray([True, False, True, True])
    args = _step_args(300)
    junk = R.Pending(_rand(1, SLOTS, KV, 7, D), _rand(2, SLOTS, KV, 7, D),
                     -jnp.abs(_rand(3, SLOTS, KV, 7)), jnp.zeros((), jnp.int32))
    one = R.retention_decode(*args, state0, keysum0, live, 1, eps=EPS, **route)
    got = R.retention_decode(*args, state0, keysum0, live, 1, eps=EPS,
                             pending=junk, **route)
    # (the interpreter's CPU program fuses a product into a sum where
    # it can, and not the same ones in both kernels: an ulp)
    for a, b in zip(one, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0,
                                   atol=2e-6 if route else 0)
    # with no `pending` at all the kernel is the single step's: one key
    # row a kv head, the value spread over the lanes
    step = jax.make_jaxpr(lambda *a: R.retention_decode(
        *a, state0, keysum0, live, 1, eps=EPS, interpret=True))(*args)
    (call,) = [e for e in step.jaxpr.eqns if "pallas" in e.primitive.name]
    assert [v.aval.shape for v in call.invars[3:7]] == [
        (SLOTS, KV, 8, D), (SLOTS, KV, D), (SLOTS, KV, D), (SLOTS, KV, D, D)]
    read = jax.make_jaxpr(lambda *a: R.retention_read(
        *a, state0, keysum0, junk, live, 1, eps=EPS, interpret=True))(*args)
    (call,) = [e for e in read.jaxpr.eqns if "pallas" in e.primitive.name]
    assert [v.aval.shape for v in call.outvars] == [
        (SLOTS, KV, 8, D), (SLOTS, KV, D, D)]


# ----------------------------------------------------------------------
# the model and the engine
# ----------------------------------------------------------------------
def _model(seed=0):
    cfg = brumby.BrumbyConfig.tiny()
    return cfg, brumby.init_params(cfg, jax.random.PRNGKey(seed), std=0.2)


def _prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).tolist() for n in lengths]


def test_pack_equals_alone_bit_for_bit():
    """Two prompts in one packed row against each alone in a row of its
    own, float32 on the CPU: the logits of each prompt's last token and
    the state left in its slot are the SAME BITS.  A segment start
    zeroes what is carried, every chunk belongs to one prompt, and a
    row's matmuls do not mix tokens, so there is nothing to round
    differently."""
    cfg, params = _model()
    p1, p2 = _prompts(13, 20)

    def run(prompts, slots, N, K=2):
        tokens, seg, posn = (np.zeros(N, np.int32), np.full(N, -1, np.int32),
                             np.zeros(N, np.int32))
        # `K` entries whatever the prompts, as the engine's programs
        # have: an unused entry reads row 0 and names no slot
        last, at = [0] * K, 0
        slots = list(slots) + [SLOTS] * (K - len(slots))
        for i, p in enumerate(prompts):
            T = len(p)
            tokens[at:at + T], seg[at:at + T] = p, i
            posn[at:at + T] = np.arange(T)
            last[i] = at + T - 1
            at += -(-T // C) * C
        logits, cache = jax.jit(
            lambda t, s, p, la, sl: brumby.forward(
                cfg, params, t[None], brumby.init_cache(cfg, SLOTS),
                packed=Packed(la, s, p), slots=sl, chunk=C)
        )(jnp.asarray(tokens), jnp.asarray(seg), jnp.asarray(posn),
          jnp.asarray(last, jnp.int32), jnp.asarray(slots, jnp.int32))
        return np.asarray(logits[0]), [np.asarray(c) for c in cache]

    both, cache = run([p1, p2], [2, 0], 48)
    for i, (p, slot) in enumerate(((p1, 2), (p2, 0))):
        alone, own = run([p], [1], 48)
        np.testing.assert_array_equal(both[i], alone[0])
        for leaf, mine in zip(cache, own):
            np.testing.assert_array_equal(leaf[:, slot], mine[:, 1])


def test_decode_continues_the_prefill():
    """Prefill of a prompt then decode steps off its slot's state give
    the logits a forward over the longer sequence gives: the state IS
    the context.  1e-4 on logits of std ~1: float32, summation order."""
    cfg, params = _model()
    (toks,) = _prompts(19)
    T0 = 11
    want, _ = brumby.forward(cfg, params, jnp.asarray([toks]), chunk=C)
    _, cache = brumby.forward(
        cfg, params, jnp.asarray([toks[:T0]]), brumby.init_cache(cfg, 2),
        slots=jnp.asarray([1], jnp.int32), chunk=C)
    for t in range(T0, len(toks)):
        lg, cache = brumby.decode_step(
            cfg, params, jnp.asarray([0, toks[t]]), cache,
            jnp.asarray([0, t]), live=jnp.asarray([False, True]))
        np.testing.assert_allclose(np.asarray(lg[1]), np.asarray(want[0, t]),
                                   atol=1e-4)
    assert float(jnp.std(want)) > 0.3


@pytest.mark.parametrize("chunk,n_new", [(2, 6), (8, 12)])
@pytest.mark.parametrize("kw", [{}, {"decode_kernel": "pallas",
                                     "kernel_interpret": True}], ids=IDS)
def test_engine_end_to_end_through_submit(kw, chunk, n_new):
    """Five requests on three slots through `submit`: the greedy tokens
    are `forward`'s (every answer fed back through it: each token is
    the argmax at the position before it; float32), the cache is per
    slot (no bytes a token, no blocks taken), admission packed several
    prompts a program, and the programs have no tables.  A chunk's
    state is written at its LAST step only: every answer runs through
    whole chunks (flushed) and ends inside one (its row dies there,
    unflushed, and the slot's next prompt starts from zero), and after
    any program the cache is the two whole leaves, nothing pending;
    `state_rows_flushed` counts a request's whole chunks."""
    cfg, params = _model()
    eng = LlamaEngine(cfg, params, slots=3, chunk=chunk, block_size=C,
                      max_len=48, **kw)
    try:
        prompts = _prompts(5, 13, 8, 20, 3)
        outs = [f.result(timeout=300)
                for f in [eng.submit(p, n_new) for p in prompts]]
        st = eng.stats()
        leaves = [c.shape for c in eng._cache]
    finally:
        eng.shutdown()
    for p, out in zip(prompts, outs):
        assert len(out) == n_new
        lg, _ = brumby.forward(cfg, params, jnp.asarray([p + out[:-1]]),
                               chunk=C)
        assert out == np.argmax(lg[0, len(p) - 1:], axis=-1).tolist()
    state, keysum = R.state_shapes(cfg.n_layers, 1, cfg.n_kv_heads,
                                   cfg.head_dim)
    assert leaves == list(R.state_shapes(cfg.n_layers, 3, cfg.n_kv_heads,
                                         cfg.head_dim))
    assert st["cache_bytes_per_token"] == 0
    assert st["cache_bytes_per_slot"] == 4 * (np.prod(state) + np.prod(keysum))
    assert st["prefix_hit_tokens"] == 0 and st["blocks_free"] == st["blocks_total"]
    assert st["prefill_rows"] == 5 and st["prefill_calls"] < 5
    assert list(eng._chunk_cache) == [0]
    ticks = [t for t in st["tick_ring"] if t["row_steps"]]
    assert ticks and all(t["gather_blocks"] == 0 for t in ticks)
    assert max(t["state_rows_live"] for t in ticks) == 3
    # a request decodes `n_new - 1` steps from a chunk's first
    assert sum(t["row_steps_live"] for t in ticks) == 5 * (n_new - 1)
    assert sum(t["state_rows_flushed"] for t in ticks) == 5 * (
        (n_new - 1) // chunk)


def test_a_chunk_of_one_step_defers_nothing():
    """`chunk` 1: the program is the single step's (no read call, no
    pending), and the tick says a state write a live row-step."""
    cfg, params = _model()
    eng = LlamaEngine(cfg, params, slots=2, chunk=1, block_size=C,
                      max_len=32, decode_kernel="pallas",
                      kernel_interpret=True)
    try:
        (p,) = _prompts(9)
        out = eng.submit(p, 4).result(timeout=300)
        ticks = [t for t in eng.stats()["tick_ring"] if t["row_steps"]]
        text = str(jax.make_jaxpr(eng._model.decode_chunk(0))(
            params, *eng._cache, eng._tok, eng._pos, eng._stop))
    finally:
        eng.shutdown()
    lg, _ = brumby.forward(cfg, params, jnp.asarray([p + out[:-1]]), chunk=C)
    assert out == np.argmax(lg[0, len(p) - 1:], axis=-1).tolist()
    assert "retention_decode" in text and "retention_read" not in text
    assert sum(t["state_rows_flushed"] for t in ticks) == 3 == sum(
        t["row_steps_live"] for t in ticks)


def test_prefix_cache_is_refused_with_a_typed_error():
    cfg, params = _model()
    with pytest.raises(PrefixCacheUnsupportedError, match="per-slot state"):
        LlamaEngine(cfg, params, slots=2, prefix_cache=True)
    assert issubclass(PrefixCacheUnsupportedError, ValueError)
    with pytest.raises(ValueError, match="int8"):
        LlamaEngine(cfg, params, slots=2, kv_dtype="int8")
    # the default asks for a prefix cache only where one can exist
    eng = LlamaEngine(cfg, params, slots=2, block_size=C, max_len=32)
    try:
        assert eng._radix is None and eng.stats()["blocks_cached"] == 0
        with pytest.raises(PrefixCacheUnsupportedError):
            eng._model.suffix_prefill(8, 1)
    finally:
        eng.shutdown()
