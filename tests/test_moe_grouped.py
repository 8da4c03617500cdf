"""The serving expert layer's grouped products (`parallel/moe.py`): the
kernel that fetches a group's matrix a GROUP ahead
(`ops/grouped_matmul.py`) and megablox's, both in the interpreter,
against `lax.ragged_dot`; and the rule that picks between them from
static shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_matmul
from ray_tpu.parallel import moe

D, I = 32, 16


def _layer(E, stacked, seed=0):
    """Weights and a router that sends a row to the expert whose column
    of `h` is largest: `h` decides the routing, exactly."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lead = (3, E) if stacked else (E,)
    return {"router": jnp.eye(D, E) * 4.0, "router_bias": jnp.zeros((E,)),
            "e_gate": jax.random.normal(ks[0], lead + (D, I)) * 0.3,
            "e_up": jax.random.normal(ks[1], lead + (D, I)) * 0.3,
            "e_down": jax.random.normal(ks[2], lead + (I, D)) * 0.3}


def _rows(case, E, top_k, N, tm):
    """`h` [N, D] for a case: noise in the columns past E, and in the
    first E columns what the case wants of the routing."""
    rng = np.random.default_rng(5)
    h = np.zeros((N, D), np.float32)
    h[:, E:] = rng.normal(size=(N, D - E))
    if case == "random":
        h[:, :E] = rng.normal(size=(N, E))
    elif case == "no-row-for-one":
        h[:, :E] = rng.normal(size=(N, E))
        h[:, 2] = -9.0
    elif case == "every-row-to-one":
        h[:, :E] = rng.normal(size=(N, E))
        h[:, 1] = 9.0
    else:  # "a-group-of-one-tile": sizes tm, 2 * tm, 0 and the rest
        assert case == "a-group-of-one-tile" and top_k == 1
        to = np.r_[np.zeros(tm), np.ones(2 * tm), np.full(N - 3 * tm, 3)]
        h[np.arange(N), rng.permutation(to).astype(int)] = 9.0
    return jnp.asarray(h)


def _visits(sizes, tm):
    """(row tile, group) pairs that share a row, counted the slow way."""
    ends = np.cumsum(sizes)
    return int(sum(-(-e // tm) - (e - s) // tm
                   for s, e in zip(sizes, ends) if s))


CASES = [
    # case, E, top_k, rows, tile: mean rows an expert against the tile
    ("random", 8, 2, 96, 16),              # 24: most groups straddle
    ("random", 4, 4, 64, 32),              # 64: groups of 2-3 tiles
    ("random", 16, 2, 8, 16),              # 1: the decode regime
    ("no-row-for-one", 8, 2, 64, 16),
    ("every-row-to-one", 4, 2, 72, 16),    # a group of 4.5 tiles
    ("a-group-of-one-tile", 4, 1, 80, 16),
]


@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stack"])
@pytest.mark.parametrize("masked", [False, True], ids=["all-rows", "row-mask"])
@pytest.mark.parametrize("case,E,top_k,N,tm", CASES,
                         ids=[f"{c[0]}-E{c[1]}-k{c[2]}-n{c[3]}-t{c[4]}"
                              for c in CASES])
def test_a_group_ahead_gives_megabloxs_result(
        monkeypatch, case, E, top_k, N, tm, masked, stacked):
    """The two kernels walk the same tiles and differ only in when a
    group's matrix arrives: the SAME bits in float32, both agreeing
    with `lax.ragged_dot`, and `stats` that count the real rows:
    `experts_touched`, `load_max`, and `tile_visits`, one a (tile,
    group) pair that share a row."""
    layer, h = _layer(E, stacked), _rows(case, E, top_k, N, tm)
    mask = np.ones(N, bool)
    if masked:
        mask[[0, 3, N // 2, N - 1]] = False
    kw = dict(top_k=top_k, scale=1.5, route_eps=1e-6, dtype=jnp.float32,
              stack_index=jnp.int32(1) if stacked else None,
              row_mask=jnp.asarray(mask) if masked else None)
    want, oracle = moe.dropless_moe(h, layer, **kw)
    _, idx = moe.sigmoid_topk_route(h, layer["router"], layer["router_bias"],
                                    top_k, 1.5, 1e-6)
    sizes = np.bincount(np.asarray(idx)[mask].ravel(), minlength=E)
    if case == "no-row-for-one":
        assert sizes[2] == 0 and (np.delete(sizes, 2) > 0).all()
    elif case == "every-row-to-one":
        assert sizes[1] == mask.sum() > 4 * tm
    elif case == "a-group-of-one-tile" and not masked:
        assert list(sizes) == [tm, 2 * tm, 0, N - 3 * tm]
    got = {}
    for ahead in (False, True):
        monkeypatch.setattr(moe, "row_tiling", lambda r, g: (tm, ahead))
        y, stats = moe.dropless_moe(h, layer, kernel=True, interpret=True,
                                    **kw)
        got[ahead] = np.asarray(y)
        assert int(stats["experts_touched"]) == (sizes > 0).sum()
        assert int(stats["load_max"]) == sizes.max()
        assert int(stats["tile_visits"]) == _visits(sizes, tm)
    np.testing.assert_array_equal(got[True], got[False])
    np.testing.assert_allclose(got[True], want, atol=2e-5, rtol=2e-5)
    assert not got[True][~mask].any()
    assert int(oracle["experts_touched"]) == (sizes > 0).sum()
    # `ragged_dot` has no tiles: it counts megablox's
    assert int(oracle["tile_visits"]) == _visits(sizes, moe.ROW_TILE)


@pytest.mark.parametrize("sizes,tm,rows", [
    ((5, 0, 20, 7), 8, 32), ((0, 0, 16, 0), 8, 24), ((3, 3, 3, 3), 8, 16),
    ((0, 0, 0, 0), 8, 16), ((40, 0, 0, 0, 0, 1), 16, 48),
], ids=["straddles", "one-group", "four-in-a-tile", "no-row", "ends-apart"])
def test_the_walk_names_each_groups_first_step_slot_and_successor(
        sizes, tm, rows):
    """What the kernel's copies hang on: a group's matrix is waited for
    at its FIRST step, in the slot the previous group did not use, and
    the next group WITH rows is asked for there; after the last, none.
    Rows past the last group are never computed."""
    walk, steps = grouped_matmul._walk(jnp.asarray(sizes, jnp.int32), rows, tm)
    _, gids, _, first, slot, nxt = (np.asarray(a) for a in walk)
    steps = int(steps)
    assert steps == _visits(np.asarray(sizes), tm)
    live = [g for g, s in enumerate(sizes) if s]
    starts = np.flatnonzero(first)
    assert (starts < steps).all() and list(gids[starts]) == live
    assert list(slot[starts]) == [i % 2 for i in range(len(live))]
    assert list(nxt[starts]) == live[1:] + [-1] * bool(live)
    for a, b in zip(starts, list(starts[1:]) + [steps]):
        assert (gids[a:b] == gids[a]).all() and (slot[a:b] == slot[a]).all()
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.normal(size=(rows, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(sizes), D, I)), jnp.float32)
    out = grouped_matmul.gmm(xs, w, jnp.asarray(sizes, jnp.int32),
                             row_tile=tm, interpret=True)
    real = sum(sizes)
    np.testing.assert_allclose(
        out[:real], jax.lax.ragged_dot(xs[:real], w, jnp.asarray(sizes)),
        atol=2e-5, rtol=2e-5)


def _grouped_products(fn, *args):
    """(kernel name, result shape) of the Pallas calls in `fn`'s trace."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"],
                              tuple(eqn.outvars[0].aval.shape)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("E,top_k,tokens,ahead", [
    (32, 4, 128, False),      # lfm2, a decode step: 16 rows an expert
    (128, 6, 64, False),      # kanana, a decode step: 3
    (128, 6, 512, False),     # kanana's smallest prefill: 24
    (32, 4, 256, True),       # lfm2's smallest prefill: 32
    (32, 4, 1296, True),      # lfm2 `n1296`: 162
    (128, 6, 1024, True),     # kanana `n1024`: 48
    (128, 6, 2320, True),     # kanana `n2320`: 108
], ids=["lfm2-decode", "kanana-decode", "kanana-n512", "lfm2-n256",
        "lfm2-n1296", "kanana-n1024", "kanana-n2320"])
def test_the_rule_reads_static_shapes_and_leaves_decode_alone(
        E, top_k, tokens, ahead):
    """A decode step's products are the parent's: megablox over
    `[slots * top_k, ...]` rows at 128 a tile (what the benchmark's
    `moe_grouped` finds them by).  A prefill's, from 32 rows an expert
    on, walk the same packed rows in tiles of 64 and fetch a group
    ahead."""
    pairs = tokens * top_k
    tile = 64 if ahead else moe.ROW_TILE
    assert moe.row_tiling(pairs, E) == (tile, ahead)
    layer = jax.eval_shape(lambda: _layer(E, True))
    layer["router"] = jax.ShapeDtypeStruct((D, E), jnp.float32)
    decode = pairs // E <= 16

    def fn(h, layer):
        return moe.dropless_moe(
            h, layer, top_k=top_k, scale=1.0, route_eps=1e-6,
            dtype=jnp.float32, kernel=True, stack_index=jnp.int32(2),
            row_mask=jnp.ones((tokens,), bool) if decode else None)

    found = _grouped_products(
        fn, jax.ShapeDtypeStruct((tokens, D), jnp.float32), layer)
    rows = -(-pairs // tile) * tile
    name = "grouped_matmul_prefetch" if ahead else None  # megablox: none
    assert found == [(name, (rows, I)), (name, (rows, I)), (name, (rows, D))]


# -- a held share: the compact form -------------------------------------
def _held_rows(case, N, lo, n, E=16):
    """`h` [N, D] for `_layer`'s router (a row goes to the experts whose
    columns of `h` are largest): random, or a share of the rows sent to
    the held experts `lo .. lo + n` with all of their pairs, the others
    to experts held elsewhere."""
    rng = np.random.default_rng(11)
    h = np.zeros((N, D), np.float32)
    h[:, E:] = rng.normal(size=(N, D - E))
    h[:, :E] = rng.normal(size=(N, E))
    if case != "random":
        here = rng.permutation(N) < int(N * case)
        away = [e for e in range(E) if not lo <= e < lo + n]
        h[np.ix_(here, range(lo, lo + n))] += 9.0
        h[np.ix_(~here, away)] += 9.0
    return jnp.asarray(h)


HELD = [
    # case (or the share of rows sent here), top_k, tokens, (lo, n), mask,
    # kernel's tile (None: `ragged_dot`), slabs walked.  `ragged_dot` at
    # the constant's 16,384 pairs; the interpreter at an eighth of it
    ("random", 4, 4096, (0, 8), False, None, 1),    # the shares of 2,
    ("random", 4, 4096, (8, 8), True, None, 1),
    ("random", 4, 4096, (4, 4), False, None, 1),    # of 4
    ("random", 4, 512, (12, 4), True, 16, 1),
    ("random", 4, 512, (6, 2), False, 16, 1),       # and of 8
    ("random", 4, 4100, (14, 2), True, None, 1),    # a last slab cut short
    (0.5, 2, 8192, (4, 2), False, None, 2),         # a skewed router:
    (0.75, 2, 8192, (4, 2), True, None, 3),         # more than a slab
    (0.75, 2, 1024, (2, 2), False, 32, 3),
    (1.0, 2, 8192, (0, 2), False, None, 4),         # every pair is held
    (0.0, 2, 8192, (8, 2), False, None, 0),         # and none is
    (0.0, 2, 1024, (8, 2), True, 32, 0),
]


@pytest.mark.parametrize(
    "case,top_k,N,held,masked,tm,passes", HELD,
    ids=[f"{c[0]}-k{c[1]}-n{c[2]}-held{c[3][0]}+{c[3][1]}"
         f"{'-row-mask' if c[4] else ''}{'-t%d' % c[5] if c[5] else ''}"
         for c in HELD])
def test_a_held_share_moves_only_its_pairs(monkeypatch, case, top_k, N, held,
                                           masked, tm, passes):
    """From `COMPACT_FROM` pairs on a held share gathers, multiplies and
    adds back its own pairs alone, a slab at a time: the pair-wide
    form's result to float32 rounding (the at most `top_k` terms of a
    token are added in another order) and `reference.routed`'s, for ANY
    routing: a router that sends this share several slabs' worth walks
    them all (`passes`), one that sends it nothing walks none and gives
    zeros.  `tile_visits` is summed over the slabs, each packed from its
    own row 0."""
    from benchmarks.reference import dots3 as ref

    E, (lo, n) = 16, held
    if tm:
        monkeypatch.setattr(moe, "COMPACT_FROM", moe.COMPACT_FROM // 8)
    assert N * top_k >= moe.COMPACT_FROM
    assert not moe.slab_rows(moe.COMPACT_FROM - 1, n, E)
    rows = moe.slab_rows(N * top_k, n, E)
    assert rows % moe.ROW_TILE == 0 and rows < N * top_k + moe.ROW_TILE
    whole = _layer(E, False)
    layer = {**whole, **{k: whole[k][lo:lo + n]
                         for k in ("e_gate", "e_up", "e_down")}}
    h = _held_rows(case, N, lo, n)
    mask = np.ones(N, bool)
    if masked:
        mask[[0, 3, N // 2, N - 1]] = False
    kw = dict(top_k=top_k, scale=1.5, route_eps=1e-6, dtype=jnp.float32,
              held=held, row_mask=jnp.asarray(mask) if masked else None)
    if tm:
        monkeypatch.setattr(moe, "row_tiling", lambda r, g: (tm, True))
        kw.update(kernel=True, interpret=True)
    y, stats = moe.dropless_moe(h, layer, **kw)
    with monkeypatch.context() as m:
        m.setattr(moe, "COMPACT_FROM", N * top_k + 1)
        wide, wide_stats = moe.dropless_moe(h, layer, **kw)
    assert int(wide_stats["passes"]) == 0
    _, idx = moe.sigmoid_topk_route(h, layer["router"], layer["router_bias"],
                                    top_k, 1.5, 1e-6)
    idx = np.asarray(idx)[mask].ravel()
    sizes = np.bincount(idx[(idx >= lo) & (idx < lo + n)] - lo, minlength=n)
    assert -(-sizes.sum() // rows) == passes == int(stats["passes"])
    for k in ("experts_touched", "load_max"):
        assert int(stats[k]) == int(wide_stats[k])
    assert int(stats["load_max"]) == sizes.max()
    # a slab's groups: each group's rows that fall inside it
    ends = np.cumsum(sizes)
    cut = [np.clip(ends, a, a + rows) - np.clip(ends - sizes, a, a + rows)
           for a in range(0, int(sizes.sum()), rows)]
    assert int(stats["tile_visits"]) == sum(
        _visits(c, tm or moe.ROW_TILE) for c in cut)
    y, wide = np.asarray(y), np.asarray(wide)
    assert np.isfinite(y).all() and not y[~mask].any()
    np.testing.assert_allclose(y, wide, atol=2e-5, rtol=2e-5)
    want = ref.routed(h, layer, top_k=top_k, scale=1.5, offset=lo,
                      quant=ref._identity)
    np.testing.assert_allclose(y[mask], np.asarray(want)[mask],
                               atol=5e-5, rtol=5e-5)
    if not passes:
        assert not y.any()


# ---------------------------------------------------------------------
# the same held share, trained: `dropless_moe_train`
# ---------------------------------------------------------------------
TRAINED = [
    # share of the rows sent here, top_k, tokens, (lo, n), kernels, slabs
    ("random", 4, 256, (0, 8), False, 1),
    ("random", 4, 256, (8, 8), True, 1),
    (0.75, 2, 512, (4, 2), False, 3),      # more than a slab's worth
    (1.0, 2, 512, (0, 2), False, 4),       # every pair is held: all slabs
    (1.0, 2, 256, (0, 2), True, 2),
    (0.0, 2, 256, (8, 2), False, 0),       # and none is
]


@pytest.mark.parametrize(
    "case,top_k,N,held,kernel,slabs", TRAINED,
    ids=[f"{c[0]}-k{c[1]}-n{c[2]}-held{c[3][0]}+{c[3][1]}"
         f"{'-kernels' if c[4] else ''}" for c in TRAINED])
def test_the_trained_share_is_the_serving_share_with_a_gradient(
        case, top_k, N, held, kernel, slabs):
    """`dropless_moe_train` gives `dropless_moe(held=)`'s result for ANY
    routing (the same pick, the same weights, no pair dropped: a router
    that sends this share several slabs' worth walks them all), counts
    the router's experts as the serving layer's `stats` do, and its
    gradient by the rows AND by the held matrices is `jax.grad`'s of the
    pair-wide `ragged_dot` form."""
    E, (lo, n) = 16, held
    whole = _layer(E, False)
    layer = {**whole, **{k: whole[k][lo:lo + n]
                         for k in ("e_gate", "e_up", "e_down")}}
    h = _held_rows(case, N, lo, n)
    kw = dict(top_k=top_k, scale=1.5, route_eps=1e-6, dtype=jnp.float32,
              held=held)
    mats = {k: layer[k] for k in ("e_gate", "e_up", "e_down")}

    def trained(h, mats):
        return moe.dropless_moe_train(
            h, {**layer, **mats}, layer["router_bias"], kernel=kernel,
            interpret=kernel, **kw)

    def served(h, mats):  # the pair-wide form: `ragged_dot`, no loop
        return moe.dropless_moe(h, {**layer, **mats}, **kw)

    (y, stats), (want, served_stats) = trained(h, mats), served(h, mats)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=2e-5)
    assert int(stats["slabs"]) == slabs
    _, idx = moe.sigmoid_topk_route(h, layer["router"], layer["router_bias"],
                                    top_k, 1.5, 1e-6)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
    np.testing.assert_array_equal(stats["counts"], counts)
    assert int(stats["held_pairs"]) == counts[lo:lo + n].sum()
    assert int(served_stats["load_max"]) == counts[lo:lo + n].max()
    dy = jax.random.normal(jax.random.PRNGKey(9), y.shape)
    got = jax.grad(lambda *a: jnp.sum(trained(*a)[0] * dy), (0, 1))(h, mats)
    ref = jax.grad(lambda *a: jnp.sum(served(*a)[0] * dy), (0, 1))(h, mats)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=3e-4)
