"""`ops/ssd.py` on the CPU in float32: the chunked scan, in plain XLA
and as the Pallas kernel in the interpreter, against the recurrence one
token at a time (`ssd_step`, and the reference's own `recurrence`, which
shares no code with it), from a non-zero state, over packed sequences
that start anywhere in a chunk, with dead rows; the kernel against the
XLA form; the convolution's scan against its step."""

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
# a state, a HEAD at a time and against that head's largest entry (a
# head that decays fast holds a small state: one limit over all heads
# would not see it); float32 sums in another order read 1e-6, a state
# rounded to bfloat16 on its way 2e-3
STATE_TOL = 2e-5
FORMS = pytest.mark.parametrize("form", ["xla", "kernel"])


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


def scan(r, seg, ends, chunk, init=None, form="xla"):
    with jax.default_matmul_precision("highest"):
        return ssd.ssd_scan(r["x"], r["dt"], r["A"], r["B"], r["C"],
                            jnp.asarray(seg), jnp.asarray(ends, jnp.int32),
                            init=init, chunk=chunk,
                            interpret=form == "kernel")


def state_off(S, want):
    """The worst head's largest error, as a share of that head's
    largest entry: S, want `[H, P, N]`."""
    S, want = np.asarray(S), np.asarray(want)
    return max(np.abs(S[h] - want[h]).max() / np.abs(want[h]).max()
               for h in range(want.shape[0]))


def test_the_step_is_the_references_recurrence(row):
    heads = lambda t: jnp.repeat(t, H // G, axis=1)  # noqa: E731
    want = ref.recurrence(row["x"], row["dt"], row["A"], heads(row["B"]),
                          heads(row["C"]))
    got, _ = stepped(row, 0, T, jnp.zeros((H, P, N)))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


@FORMS
@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
def test_the_chunked_scan_from_a_state_equals_a_token_at_a_time(row, chunk,
                                                                form):
    """One sequence of 37 tokens (padding behind it; 44 rows are no
    whole chunks of 8, 16 or 64) from a NON-ZERO state: every output and
    the state after its last token, which lies inside a chunk."""
    init = jnp.asarray(np.random.default_rng(5).normal(size=(H, P, N)),
                       jnp.float32)
    seg = np.full(T, -1, np.int32)
    seg[:37] = 0
    y, S = scan(row, seg, [36], chunk, init, form)
    want, S_want = stepped(row, 0, 37, init)
    assert np.abs(np.asarray(y[:37]) - np.asarray(want)).max() < TOL
    assert np.abs(np.asarray(S[0]) - np.asarray(S_want)).max() < TOL
    assert state_off(S[0], S_want) < STATE_TOL
    # from zero: another result (the state is read)
    y0, _ = scan(row, seg, [36], chunk, form=form)
    assert np.abs(np.asarray(y0[:37]) - np.asarray(want)).max() > 100 * TOL


@FORMS
@pytest.mark.parametrize("chunk", [8, 16])
def test_packed_sequences_reset_where_they_start(row, chunk, form):
    """Three sequences end to end, starting at 0, 16 (a chunk's edge at
    chunk 8 and 16) and 24 + 4 = 28 (inside a chunk), each ending inside
    one: each one's outputs and end state are those it has alone from
    zero."""
    spans = [(0, 11), (16, 27), (28, 43)]
    seg = np.full(T, -1, np.int32)
    for i, (lo, hi) in enumerate(spans):
        seg[lo:hi] = i
    y, S = scan(row, seg, [hi - 1 for _, hi in spans], chunk, form=form)
    for i, (lo, hi) in enumerate(spans):
        want, S_want = stepped(row, lo, hi, jnp.zeros((H, P, N)))
        assert np.abs(np.asarray(y[lo:hi]) - np.asarray(want)).max() < TOL
        assert np.abs(np.asarray(S[i]) - np.asarray(S_want)).max() < TOL
        assert state_off(S[i], S_want) < STATE_TOL


@pytest.mark.parametrize("chunk,init", [(8, False), (8, True), (16, True),
                                        (64, False)])
def test_the_kernel_equals_the_xla_form(row, chunk, init):
    """The same row through both forms: a continued sequence 0 that ends
    inside a chunk, a second that starts there and runs to the row's
    last chunk, padding between and behind them."""
    init = init and jnp.asarray(
        np.random.default_rng(9).normal(size=(H, P, N)), jnp.float32)
    seg = np.full(T, -1, np.int32)
    seg[:13], seg[14:41] = 0, 1
    args = (row, seg, [12, 40], chunk, None if init is False else init)
    (y, S), (yk, Sk) = scan(*args), scan(*args, form="kernel")
    real = seg >= 0
    assert np.abs(np.asarray(yk) - np.asarray(y))[real].max() < TOL / 5
    for k in range(2):
        assert state_off(Sk[k], S[k]) < STATE_TOL


@pytest.mark.parametrize("heads,width,groups,block", [
    (8, 8, 2, 2),      # two blocks of heads a group
    (4, 128, 2, 2),    # a head fills its lanes alone
    (6, 8, 2, 3),      # three heads side by side
    (4, 8, 4, 1),      # a head a group
])
def test_the_kernel_at_other_head_shapes(monkeypatch, heads, width, groups,
                                         block):
    """How many heads a grid step takes and how many share a tile of
    lanes follow from the shapes: each such form against the XLA form
    and a token at a time."""
    rng = np.random.default_rng(11)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    n, Tn = 16, 21
    r = dict(x=f(Tn, heads, width), dt=jax.nn.softplus(f(Tn, heads)),
             A=-jnp.asarray(rng.uniform(0.5, 4.0, size=heads), jnp.float32),
             B=f(Tn, groups, n), C=f(Tn, groups, n))
    init = f(heads, width, n)
    seg = np.zeros(Tn, np.int32)
    monkeypatch.setattr(ssd, "_head_block", lambda *_: block)
    args = (r, seg, [Tn - 1], 8, init)
    (y, S), (yk, Sk) = scan(*args), scan(*args, form="kernel")
    want, S_want = stepped(r, 0, Tn, init)
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(yk) - np.asarray(want)).max() < TOL * scale
    assert np.abs(np.asarray(yk) - np.asarray(y)).max() < TOL * scale
    assert state_off(Sk[0], S_want) < STATE_TOL


def test_a_state_rounded_to_bfloat16_fails_the_states_limit(row):
    """What the cell's `correct` cannot tell (PERF.md section 7): a
    float32 state that went through bfloat16 is outside `STATE_TOL` in
    EVERY head, where the kernel's own is inside by a factor of ten."""
    init = jnp.asarray(np.random.default_rng(5).normal(size=(H, P, N)),
                       jnp.float32)
    seg = np.zeros(T, np.int32)
    _, S = scan(row, seg, [T - 1], 8, init, "kernel")
    _, S_want = stepped(row, 0, T, init)
    assert state_off(S[0], S_want) < STATE_TOL / 10
    rounded = np.asarray(S[0].astype(jnp.bfloat16).astype(jnp.float32))
    for h in range(H):
        assert state_off(rounded[h:h + 1], S_want[h:h + 1]) > 10 * STATE_TOL


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
