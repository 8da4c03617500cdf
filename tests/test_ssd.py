"""`ops/ssd.py` on the CPU in float32: the chunked scan against the
recurrence one token at a time (`ssd_step`, and the reference's own
`recurrence`, which shares no code with it), from a non-zero state, over
packed sequences that start anywhere in a chunk, with dead rows; the
convolution's scan against its step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as ref
from ray_tpu.ops import ssd

T, H, P, G, N = 44, 4, 8, 2, 16
# float32 sums in another order (a masked product a chunk against a
# token at a time)
TOL = 5e-5  # outputs of order 10


@pytest.fixture(scope="module")
def row():
    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return dict(x=f(T, H, P), dt=jax.nn.softplus(f(T, H)),
                A=-jnp.asarray(rng.uniform(0.5, 4.0, size=H), jnp.float32),
                B=f(T, G, N), C=f(T, G, N))


def stepped(r, lo, hi, S):
    """Tokens `lo .. hi` one at a time from state `S`."""
    ys = []
    for t in range(lo, hi):
        y, S = ssd.ssd_step(S[None], r["x"][t][None], r["dt"][t][None],
                            r["A"], r["B"][t][None], r["C"][t][None])
        S = S[0]
        ys.append(y[0])
    return jnp.stack(ys), S


def scan(r, seg, ends, chunk, init=None):
    with jax.default_matmul_precision("highest"):
        return ssd.ssd_scan(r["x"], r["dt"], r["A"], r["B"], r["C"],
                            jnp.asarray(seg), jnp.asarray(ends, jnp.int32),
                            init=init, chunk=chunk)


def test_the_step_is_the_references_recurrence(row):
    heads = lambda t: jnp.repeat(t, H // G, axis=1)  # noqa: E731
    want = ref.recurrence(row["x"], row["dt"], row["A"], heads(row["B"]),
                          heads(row["C"]))
    got, _ = stepped(row, 0, T, jnp.zeros((H, P, N)))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
def test_the_chunked_scan_from_a_state_equals_a_token_at_a_time(row, chunk):
    """One sequence of 37 tokens (padding behind it) from a NON-ZERO
    state: every output and the state after its last token."""
    init = jnp.asarray(np.random.default_rng(5).normal(size=(H, P, N)),
                       jnp.float32)
    seg = np.full(T, -1, np.int32)
    seg[:37] = 0
    y, S = scan(row, seg, [36], chunk, init)
    want, S_want = stepped(row, 0, 37, init)
    assert np.abs(np.asarray(y[:37]) - np.asarray(want)).max() < TOL
    assert np.abs(np.asarray(S[0]) - np.asarray(S_want)).max() < TOL
    # from zero: another result (the state is read)
    y0, _ = scan(row, seg, [36], chunk)
    assert np.abs(np.asarray(y0[:37]) - np.asarray(want)).max() > 100 * TOL


@pytest.mark.parametrize("chunk", [8, 16])
def test_packed_sequences_reset_where_they_start(row, chunk):
    """Three sequences end to end, starting at 0, 16 (a chunk's edge at
    chunk 8 and 16) and 24 + 4 = 28 (inside a chunk): each one's outputs
    and end state are those it has alone from zero."""
    spans = [(0, 11), (16, 27), (28, 43)]
    seg = np.full(T, -1, np.int32)
    for i, (lo, hi) in enumerate(spans):
        seg[lo:hi] = i
    y, S = scan(row, seg, [hi - 1 for _, hi in spans], chunk)
    for i, (lo, hi) in enumerate(spans):
        want, S_want = stepped(row, lo, hi, jnp.zeros((H, P, N)))
        assert np.abs(np.asarray(y[lo:hi]) - np.asarray(want)).max() < TOL
        assert np.abs(np.asarray(S[i]) - np.asarray(S_want)).max() < TOL


def test_a_dead_row_keeps_its_state(row):
    S = jnp.asarray(np.random.default_rng(6).normal(size=(3, H, P, N)),
                    jnp.float32)
    take = lambda k: jnp.stack([row[k][t] for t in (1, 2, 3)])  # noqa: E731
    live = jnp.asarray([True, False, True])
    _, new = ssd.ssd_step(S, take("x"), take("dt"), row["A"], take("B"),
                          take("C"), live)
    assert np.array_equal(np.asarray(new[1]), np.asarray(S[1]))
    assert not np.array_equal(np.asarray(new[0]), np.asarray(S[0]))
    conv = jnp.ones((3, 3, 6))
    _, rolled = ssd.conv_step(conv, jnp.zeros((3, 6)), jnp.ones((4, 6)),
                              jnp.zeros((6,)), live)
    assert np.array_equal(np.asarray(rolled[1]), np.asarray(conv[1]))
    assert float(rolled[0, -1, 0]) == 0.0 and float(rolled[0, 0, 0]) == 1.0


def test_the_convolution_scans_as_it_steps_and_as_four_shifted_products():
    rng = np.random.default_rng(8)
    C = 6
    x = jnp.asarray(rng.normal(size=(T, C)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, C)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(C,)), jnp.float32)

    def steps(lo, hi, state):
        ys = []
        for t in range(lo, hi):
            y, state = ssd.conv_step(state[None], x[t][None], w, b)
            state = state[0]
            ys.append(y[0])
        return jnp.stack(ys), state

    spans = [(0, 2), (16, 35)]
    seg = np.full(T, -1, np.int32)
    for i, (lo, hi) in enumerate(spans):
        seg[lo:hi] = i
    y, held = ssd.conv_scan(x, w, b, jnp.asarray(seg),
                            jnp.asarray([1, 34]))
    for i, (lo, hi) in enumerate(spans):
        want, s_want = steps(lo, hi, jnp.zeros((3, C)))
        assert np.abs(np.asarray(y[lo:hi]) - np.asarray(want)).max() < 1e-6
        assert np.array_equal(np.asarray(held[i]), np.asarray(s_want))
        plain = jax.nn.silu(ref.conv_shifted(x[lo:hi], w, b))
        assert np.abs(np.asarray(want) - np.asarray(plain)).max() < 1e-6
    # a sequence continued: the first taps read the state
    prev = jnp.asarray(rng.normal(size=(3, C)), jnp.float32)
    seg = np.full(T, -1, np.int32)
    seg[:9] = 0
    y, held = ssd.conv_scan(x, w, b, jnp.asarray(seg), jnp.asarray([8]),
                            prev=prev)
    want, s_want = steps(0, 9, prev)
    assert np.abs(np.asarray(y[:9]) - np.asarray(want)).max() < 1e-6
    assert np.array_equal(np.asarray(held[0]), np.asarray(s_want))
