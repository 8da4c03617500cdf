"""Fused paged flash-decode kernel parity (CPU interpret mode).

The contract (`ops/paged_attention.py`): the Pallas split-KV kernel
attending straight into the `BlockPool` tensor must reproduce the
gather (dense-view `decode_step_rows`) reference route — dense-reference numerics at
fp32/bf16 across ragged block tables and partial last blocks, greedy
engine outputs BIT-IDENTICAL kernel on vs off, and the int8 KV/weight
planes gated on argmax-match plus bounded logit error.  Interpret mode
is this file's explicit choice (`interpret=True` / `kernel_interpret=
True`): the program's default is the compiled kernel
(RT008: all RNGs seeded).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.ops import paged_attention as pa  # noqa: E402
from ray_tpu.serve.config import LLMEngineConfig  # noqa: E402
from ray_tpu.serve.engine_model import PagedKV  # noqa: E402
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


def _dense_reference(q, k, v, pos):
    """f32 softmax attention over each row's first pos[b]+1 tokens;
    GQA q [B,H,hd] against k/v [B,T,KV,hd]."""
    B, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    out = np.zeros((B, H, v.shape[-1]), np.float32)
    qf = np.asarray(q, np.float32)
    kf = np.asarray(k, np.float32)
    vf = np.asarray(v, np.float32)
    for b in range(B):
        n = int(pos[b]) + 1
        for h in range(H):
            g = h // group
            s = (kf[b, :n, g] @ qf[b, h]) * (hd ** -0.5)
            w = np.exp(s - s.max())
            w /= w.sum()
            out[b, h] = w @ vf[b, :n, g]
    return out


# block size, table width, kv heads, query heads.  The kernel folds P
# pages a compute block, the most the score tile `[H, tokens * KV]`
# allows (`pa._pages_per_block`): "w3" is one block (the shape this test
# began with), "w1" / "w2" tables narrower than any block; "w11" and
# "w81" carry the serving forms' head counts (32 query heads on 8 kv
# heads, 16 on 16), so that the tile binds as it does there: "w11" walks
# 8 + 3 pages and "w81" 32 + 32 + 17, widths that are a multiple of no P
_RAGGED = {
    "w3-gqa2": (4, 3, 2, 4),
    "w1-gqa2": (8, 1, 2, 4),
    "w2-mha": (8, 2, 2, 2),
    "w11-gqa4": (16, 11, 8, 32),
    "w11-mha": (16, 11, 16, 16),
    "w81-gqa4": (4, 81, 8, 32),
    "w81-mha": (4, 81, 16, 16),
}


@pytest.mark.parametrize("shape,pages", [
    ("w3-gqa2", 3), ("w1-gqa2", 1), ("w2-mha", 2), ("w11-gqa4", 8),
    ("w11-mha", 8), ("w81-gqa4", 32), ("w81-mha", 32)])
def test_the_ragged_shapes_walk_the_blocks_they_say(shape, pages):
    BS, W, KV, H = _RAGGED[shape]
    assert pa._pages_per_block(BS, KV, H, W) == pages


def _ragged_case(BS, W, KV, hd, seed, pad_page=0, hd_v=None):
    """A random pool and five rows over it: a row at position 0, one
    whose last live page is partial, one that fills all W pages, an
    IDLE row (position 0, its table all scratch padding) and one that
    ends on a page's first token.  Table entries past a row's live
    pages hold `pad_page`, as the engine pads with the scratch block."""
    rng = np.random.default_rng(seed)
    cap = W * BS
    pos = np.asarray([0, min(cap - 1, BS * (W - 1) + BS // 2), cap - 1,
                      0, BS * (W // 2)], np.int32)
    B = len(pos)
    NB = 2 + B * W
    tables = np.full((B, W), pad_page, np.int32)
    pages = rng.permutation(np.arange(2, NB))
    for b in range(B):
        if b == 3:
            continue
        n = pos[b] // BS + 1
        tables[b, :n], pages = pages[:n], pages[n:]
    k = rng.standard_normal((1, NB, BS, KV, hd)).astype(np.float32)
    v = rng.standard_normal((1, NB, BS, KV, hd_v or hd)).astype(np.float32)
    return k, v, tables, pos


# keys wider than values (`models/mimo_v2.py`: 192 / 128), on pools that
# fold a token's heads into one row each, `[.., KV * hd_k]` and `[.., KV *
# hd_v]`: (hd_k, hd_v) of the cases called "wide-k"
WIDE_K = (24, 16)


def _folded(pool):
    """`[L, NB, BS, KV, hd]` as the folded pool `[L, NB, BS, KV * hd]`."""
    return pool.reshape(pool.shape[:3] + (-1,))


def _rows(pool, tables):
    """Each row's dense view [B, W * BS, KV, hd] through its table."""
    B, W = tables.shape
    return np.asarray(pool)[0][tables].reshape(
        (B, -1) + tuple(pool.shape[3:]))


def _pools(k, v, dtype, int8):
    """(pools, scale kwargs, dense f32 pools the kernel should see)."""
    kd, vd = jnp.asarray(k).astype(dtype), jnp.asarray(v).astype(dtype)
    if not int8:
        return (kd, vd), {}, (np.asarray(kd, np.float32),
                              np.asarray(vd, np.float32))
    (kq, ks), (vq, vs) = pa.quantize_int8(kd), pa.quantize_int8(vd)
    return (kq, vq), dict(k_scale=ks, v_scale=vs), (
        np.asarray(pa.dequantize_int8(kq, ks, jnp.float32)),
        np.asarray(pa.dequantize_int8(vq, vs, jnp.float32)))


@pytest.mark.parametrize("kv", ["model", "int8", "wide-k"])
@pytest.mark.parametrize("shape", sorted(_RAGGED))
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_kernel_matches_dense_reference_ragged(dtype, tol, shape, kv):
    """Ragged positions (different live lengths, partial last pages,
    shuffled non-contiguous block tables, an idle row) against a dense
    softmax, at table widths around and between compute blocks.
    "wide-k": keys wider than values, on folded pools."""
    BS, W, KV, H = _RAGGED[shape]
    hd, hd_v = WIDE_K if kv == "wide-k" else (16, 16)
    k, v, tables, pos = _ragged_case(BS, W, KV, hd, seed=7, hd_v=hd_v)
    (kp, vp), scales, (kf, vf) = _pools(k, v, dtype, kv == "int8")
    if kv == "wide-k":
        kp, vp = _folded(kp), _folded(vp)
    q = np.random.default_rng(8).standard_normal(
        (len(pos), H, hd)).astype(np.float32)
    qd = jnp.asarray(q).astype(dtype)
    out = pa.paged_decode_attention(
        qd, kp, vp, jnp.asarray(tables), jnp.asarray(pos), 0,
        interpret=True, **scales,
    )
    assert out.dtype == dtype and out.shape == q.shape[:2] + (hd_v,)
    ref = _dense_reference(np.asarray(qd, np.float32), _rows(kf, tables),
                           _rows(vf, tables), pos)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("kv", ["model", "int8"])
@pytest.mark.parametrize("shape", ["w3-gqa2", "w11-gqa4", "w81-mha"])
def test_kernel_never_reads_dead_pages_into_a_result(shape, kv):
    """Every page past a row's position — the scratch block its table
    is padded with, and every pool block no row owns — is poisoned
    (NaN in K and V; under int8 in the scales, which is where a NaN
    can live).  A row's result is finite and BIT-equal to the result
    over a clean pool: dead pages carry no weight because they are
    never part of the sum, not because their weight rounds to zero."""
    BS, W, KV, H = _RAGGED[shape]
    hd = 16
    k, v, tables, pos = _ragged_case(BS, W, KV, hd, seed=13)
    live = np.zeros(k.shape[1], bool)
    for b in range(len(pos)):
        live[tables[b, :pos[b] // BS + 1]] = b != 3
    q = jnp.asarray(np.random.default_rng(14).standard_normal(
        (len(pos), H, hd)), jnp.bfloat16)

    def run(poisoned):
        (kp, vp), scales, _ = _pools(k, v, jnp.bfloat16, kv == "int8")
        if poisoned:
            dead = jnp.asarray(~live)[None, :, None, None]
            if scales:
                scales = {n: jnp.where(dead, jnp.nan, s)
                          for n, s in scales.items()}
                kp = jnp.where(dead[..., None], 127, kp)
                vp = jnp.where(dead[..., None], 127, vp)
            else:
                kp = jnp.where(dead[..., None], jnp.nan, kp)
                vp = jnp.where(dead[..., None], jnp.nan, vp)
        return np.asarray(pa.paged_decode_attention(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(pos), 0,
            interpret=True, **scales), np.float32)

    clean, poisoned = run(False), run(True)
    rows = [0, 1, 2, 4]  # row 3 is idle: it attends the scratch block
    assert np.isfinite(poisoned[rows]).all()
    np.testing.assert_array_equal(poisoned[rows], clean[rows])


# The folded forms as the kernel sees them: ONE kv head whose row holds a
# token's heads side by side, so the score tile is `[H, tokens]` and the
# block is the longest of all (`_pages_per_block`): 32 pages up to 64
# query heads, 16 at 128.  (query heads, kv heads, key width, value
# width, table width): tables a few pages wider than two blocks
_LONG_BLOCKS = {
    "h32-kv8": (32, 8, 16, 16, 70),           # the hybrid's: 512 lanes
    "h64-kv4-wide-k": (64, 4, 24, 16, 70),    # keys wider than values
    "h128-kv4": (128, 4, 16, 16, 38),         # 4 positions x 32 heads a row
    "h128-kv4-wide-k": (128, 4, 24, 16, 38),
}


def _long_block_case(H, KV, hd, hd_v, W, seed, BS=16):
    """Folded pools and six rows placed around the block's edges: a row
    that ends on a block boundary, a DEAD row between live ones, a row
    one token past the boundary, one that ends inside a page of the
    second block, one shorter than a block, one that fills the table."""
    T = pa._pages_per_block(BS, 1, H, W) * BS
    pos = np.asarray([T - 1, -1, T, T + BS + 5, T // 2 - 3, W * BS - 1],
                     np.int32)
    rng = np.random.default_rng(seed)
    B = len(pos)
    NB = 2 + B * W
    tables = np.zeros((B, W), np.int32)
    pages = rng.permutation(np.arange(2, NB))
    for b in range(B):
        n = (pos[b] + BS) // BS
        tables[b, :n], pages = pages[:n], pages[n:]
    k = rng.standard_normal((1, NB, BS, KV, hd)).astype(np.float32)
    v = rng.standard_normal((1, NB, BS, KV, hd_v)).astype(np.float32)
    q = rng.standard_normal((B, H, hd)).astype(np.float32)
    return T, q, k, v, tables, pos


@pytest.mark.parametrize("form", sorted(_LONG_BLOCKS))
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_folded_walk_in_its_longest_blocks(dtype, tol, form):
    """The folded forms at blocks of 32 and 16 pages, rows ending on,
    one past and well inside a block, against the dense softmax; and
    the same call walked 128 tokens a block, as it was before the block
    followed the score tile: a head over the results picks the same
    token for every row."""
    H, KV, hd, hd_v, W = _LONG_BLOCKS[form]
    T, q, k, v, tables, pos = _long_block_case(H, KV, hd, hd_v, W, seed=41)
    assert T == (256 if H == 128 else 512) and 2 * T < W * 16
    (kp, vp), _, (kf, vf) = _pools(k, v, dtype, False)
    qd = jnp.asarray(q).astype(dtype)

    def run():
        pa._build_attention.cache_clear()
        return np.asarray(pa.paged_decode_attention(
            qd, _folded(kp), _folded(vp), jnp.asarray(tables),
            jnp.asarray(pos), 0, interpret=True), np.float32)

    out = run()
    live = pos >= 0
    ref = _dense_reference(np.asarray(qd, np.float32)[live],
                           _rows(kf, tables)[live], _rows(vf, tables)[live],
                           pos[live])
    np.testing.assert_allclose(out[live], ref, rtol=tol, atol=tol)
    assert not out[~live].any()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "_BLOCK_TOKENS", 128)
        assert pa._pages_per_block(16, 1, H, W) == 8
        short = run()
    pa._build_attention.cache_clear()
    np.testing.assert_allclose(out, short, rtol=tol, atol=tol)
    head = np.random.default_rng(43).standard_normal(
        (H * hd_v, 97)).astype(np.float32)
    np.testing.assert_array_equal(
        (out.reshape(len(pos), -1) @ head)[live].argmax(-1),
        (short.reshape(len(pos), -1) @ head)[live].argmax(-1))


def _shape(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _per_head(B, H, KV, int8=False):
    """(q, pools, int8 scales) of a per-head form, as shapes."""
    pool = _shape(1, 4, 16, KV, 128, dtype=jnp.int8 if int8 else jnp.bfloat16)
    scale = _shape(1, 4, 16, KV, dtype=jnp.float32)
    return (_shape(B, H, 128), (pool, pool),
            {"k_scale": scale, "v_scale": scale} if int8 else {})


def _one_head(B, H, hd, *lanes):
    """... of a form the kernel sees as one kv head: a folded K and V
    pool, or the ONE latent pool."""
    return (_shape(B, H, hd), tuple(_shape(1, 4, 16, n) for n in lanes), {})


def _attend(q, pools, tables, pos, **scales):
    if len(pools) == 1:
        return pa.mla_paged_decode_attention(
            q, *pools, tables, pos, 0, value_dim=512, scale=192 ** -0.5)
    return pa.paged_decode_attention(q, *pools, tables, pos, 0, **scales)


# every form the engine serves, at its cell's (or `chip_smoke.py`'s)
# shapes: the call's shapes, the table's width, and (pages a block, H,
# KV as the kernel sees it)
_BLOCK_TABLE = {
    "mistral7b-w16": (_per_head(64, 32, 8), 16, (8, 32, 8)),
    "mistral7b-w81": (_per_head(64, 32, 8), 81, (8, 32, 8)),
    "mistral7b-int8": (_per_head(64, 32, 8, int8=True), 81, (8, 32, 8)),
    "llama1b4": (_per_head(32, 16, 16), 11, (8, 16, 16)),
    "llama1b4-int8": (_per_head(32, 16, 16, int8=True), 11, (8, 16, 16)),
    "kanana2-latent": (_one_head(64, 32, 576, 640), 145, (32, 32, 1)),
    "mimo25-folded": (_one_head(128, 64, 192, 768, 512), 545, (32, 64, 1)),
    "lfm2-folded": (_one_head(128, 32, 64, 512, 512), 81, (32, 32, 1)),
    "sdar-folded": (_one_head(128, 128, 128, 512, 512), 81, (16, 128, 1)),
    "a-table-narrower-than-a-block": (
        _one_head(128, 64, 192, 768, 512), 5, (5, 64, 1)),
}


@pytest.mark.parametrize("form", sorted(_BLOCK_TABLE))
def test_the_block_is_the_longest_the_shapes_allow(form, monkeypatch):
    """The rule's table, read where the kernels ask it: every form's
    call is traced at its serving shapes (no kernel runs) and each
    question put to `_pages_per_block` is kept.  The per-head forms,
    int8 or not, and the latent one get the pages they always got; the
    folded ones the longest block their score tile allows; and the int8
    wrapper, which lays the scales out by blocks, asks with the
    arguments the kernel's builder asks with."""
    (q, pools, scales), W, (pages, H, KV) = _BLOCK_TABLE[form]
    asked = []
    rule = pa._pages_per_block

    def keep(*a):
        asked.append((a, rule(*a)))
        return asked[-1][1]

    monkeypatch.setattr(pa, "_pages_per_block", keep)
    pa._build_attention.cache_clear()
    B = q.shape[0]
    jax.eval_shape(_attend, q, pools, _shape(B, W, dtype=jnp.int32),
                   _shape(B, dtype=jnp.int32), **scales)
    pa._build_attention.cache_clear()
    assert len(asked) == (2 if scales else 1)
    assert set(asked) == {((16, KV, H, W), pages)}


def test_append_writes_one_row_and_preserves_rest():
    """The aliased in-place append touches EXACTLY the (block, slot)
    each row's position names — every other pool entry is bit-equal —
    and an overshot position (>= table capacity) writes nothing, the
    same dropped-write the gather route's clamp produces."""
    B, KV, hd, BS, NB, W = 3, 2, 8, 4, 8, 2
    rng = np.random.default_rng(11)
    kp0 = rng.standard_normal((1, NB, BS, KV, hd)).astype(np.float32)
    vp0 = rng.standard_normal((1, NB, BS, KV, hd)).astype(np.float32)
    tables = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    pos = jnp.asarray([0, 5, W * BS], jnp.int32)  # row 2 overshoots
    k_new = rng.standard_normal((B, KV, hd)).astype(np.float32)
    v_new = rng.standard_normal((B, KV, hd)).astype(np.float32)
    kp, vp = pa.paged_kv_append(
        jnp.asarray(kp0), jnp.asarray(vp0), jnp.asarray(k_new),
        jnp.asarray(v_new), tables, pos, 0, interpret=True
    )
    ek, ev = kp0.copy(), vp0.copy()
    ek[0, 1, 0], ev[0, 1, 0] = k_new[0], v_new[0]  # pos 0 -> blk 1 slot 0
    ek[0, 4, 1], ev[0, 4, 1] = k_new[1], v_new[1]  # pos 5 -> blk 4 slot 1
    np.testing.assert_array_equal(np.asarray(kp), ek)
    np.testing.assert_array_equal(np.asarray(vp), ev)



def _dead_case(kind, BS, W, KV, H, hd, seed):
    """(call, tables, pos): `call(tables, pos)` runs the decode
    attention of `kind` (per-head bf16, per-head int8, latent) over one
    random pool."""
    if kind == "wide-k":
        hd, hd_v = WIDE_K
        k, v, tables, pos = _ragged_case(BS, W, KV, hd, seed, hd_v=hd_v)
    else:
        k, v, tables, pos = _ragged_case(BS, W, KV, hd, seed)
    q = jnp.asarray(np.random.default_rng(seed + 1).standard_normal(
        (len(pos), H, hd)), jnp.bfloat16)
    if kind == "latent":
        pool = jnp.asarray(k[:, :, :, 0], jnp.bfloat16)  # [1, NB, BS, hd]
        pool = jnp.pad(pool, ((0, 0),) * 3 + ((0, 128 - hd),))

        def call(tables, pos):
            return pa.mla_paged_decode_attention(
                q, pool, jnp.asarray(tables), jnp.asarray(pos), 0,
                value_dim=hd - 4, scale=0.25, interpret=True)
    else:
        (kp, vp), scales, _ = _pools(k, v, jnp.bfloat16, kind == "int8")
        if kind == "wide-k":
            kp, vp = _folded(kp), _folded(vp)

        def call(tables, pos):
            return pa.paged_decode_attention(
                q, kp, vp, jnp.asarray(tables), jnp.asarray(pos), 0,
                interpret=True, **scales)
    return call, tables, pos


@pytest.mark.parametrize("dead", [(0, 2, 3), (1, 4)], ids=["first", "last"])
@pytest.mark.parametrize("dead_pos", [-1, 0], ids=["no-block", "one-block"])
@pytest.mark.parametrize("kind", ["model", "int8", "latent", "wide-k"])
def test_dead_rows_leave_live_rows_bit_identical(kind, dead_pos, dead):
    """Rows that owe no token, before, between and behind live rows
    (the walk prefetches across rows): handed -1 over a scratch table
    they read nothing and give a row of zeros (the engine's form, see
    `dead_row_positions`); handed 0 they read one block.  Either way
    every other row's result is bit-equal to what it is beside rows
    that walk their tables."""
    BS, W, KV, H = _RAGGED["w11-gqa4"]
    if kind == "latent":
        KV = 1
    call, tables, pos = _dead_case(kind, BS, W, KV, H, 16, seed=21)
    want = np.asarray(call(tables, pos), np.float32)
    t2, p2 = tables.copy(), pos.copy()
    for b in dead:
        t2[b], p2[b] = 0, dead_pos  # the scratch block, W times
    got = np.asarray(call(t2, p2), np.float32)
    live = [b for b in range(len(pos)) if b not in dead]
    np.testing.assert_array_equal(got[live], want[live])
    assert np.isfinite(got).all()
    if dead_pos < 0:
        assert not got[list(dead)].any()


@pytest.mark.parametrize("kind", ["model", "int8", "latent", "wide-k"])
def test_append_writes_nothing_for_a_dead_row(kind):
    """`dead_row_positions` hands a dead row the first position past its
    table's reach: the append kernels reject it, so the pool (and the
    int8 sidecar) is bit-equal except for the live rows' one row each,
    though the dead rows hold REAL tables, as a finished row does whose
    harvest lags."""
    B, KV, hd, BS, NB, W = 4, 2, 8, 4, 10, 2
    rng = np.random.default_rng(31)
    tables = jnp.asarray([[1, 2], [3, 4], [5, 6], [7, 8]], jnp.int32)
    pos = jnp.asarray([0, 5, 2, 7], jnp.int32)
    live = jnp.asarray([True, False, True, False])
    w_pos, a_pos = pa.dead_row_positions(pos, live, tables, BS)
    assert list(np.asarray(w_pos)) == [0, W * BS, 2, W * BS]
    assert list(np.asarray(a_pos)) == [0, -1, 2, -1]
    if kind == "latent":
        pool0 = jnp.asarray(rng.standard_normal((1, NB, BS, 128)),
                            jnp.bfloat16)
        new = jnp.asarray(rng.standard_normal((B, 100)), jnp.bfloat16)
        got = [pa.mla_paged_kv_append(pool0, new, tables, w_pos, 0,
                                      interpret=True)]
        want = [pa.mla_paged_kv_append(pool0, new[::2], tables[::2],
                                       pos[::2], 0, interpret=True)]
    else:
        hd_v = hd
        if kind == "wide-k":
            hd, hd_v = WIDE_K
        k0 = rng.standard_normal((1, NB, BS, KV, hd)).astype(np.float32)
        v0 = rng.standard_normal((1, NB, BS, KV, hd_v)).astype(np.float32)
        (kp, vp), scales, _ = _pools(k0, v0, jnp.float32, kind == "int8")
        kn = jnp.asarray(rng.standard_normal((B, KV, hd)), jnp.float32)
        vn = jnp.asarray(rng.standard_normal((B, KV, hd_v)), jnp.float32)
        if kind == "wide-k":  # folded pools take the rows as they are
            kp, vp = _folded(kp), _folded(vp)
        new_scales = [{}, {}]
        if scales:
            (kn, ksn), (vn, vsn) = pa.quantize_int8(kn), pa.quantize_int8(vn)
            new_scales = [dict(k_new_scale=ksn[sl], v_new_scale=vsn[sl],
                               **scales) for sl in (slice(None),
                                                    slice(None, None, 2))]
        got = pa.paged_kv_append(kp, vp, kn, vn, tables, w_pos, 0,
                                 interpret=True, **new_scales[0])
        # the oracle: the live rows alone
        want = pa.paged_kv_append(kp, vp, kn[::2], vn[::2], tables[::2],
                                  pos[::2], 0, interpret=True,
                                  **new_scales[1])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_quantize_int8_idempotent_and_bounded():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 16)).astype(np.float32))
    q, s = pa.quantize_int8(x)
    assert q.dtype == jnp.int8 and s.shape == (4,)
    deq = pa.dequantize_int8(q, s, jnp.float32)
    # error bounded by half a quantization step per row
    step = np.asarray(s)[:, None]
    assert np.max(np.abs(np.asarray(deq) - np.asarray(x))) <= \
        0.5 * step.max() + 1e-7
    # requantizing the dequantized payload is exact (engine safety:
    # the gather fallback round-trips untouched rows through this)
    q2, s2 = pa.quantize_int8(deq)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), rtol=1e-6)


def _per_head_kv(cfg, block_size, kv_dtype, dtype=None):
    tail = (cfg.n_kv_heads, cfg.head_dim)
    return PagedKV({"k": tail, "v": tail}, dtype or cfg.dtype, block_size,
                   kv_dtype)


def _empty_pool(kv, layers, num_blocks):
    return tuple(jnp.zeros((layers, num_blocks, kv.block_size) + leaf.tail,
                           leaf.dtype) for leaf in kv.leaves)


@pytest.mark.parametrize("dead", [(), (1,)], ids=["all-live", "a-dead-row"])
@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
@pytest.mark.parametrize("route", ["dense-view", "paged-interpret"])
def test_decode_step_rows_matches_the_scalar_oracle(model, route, kv_dtype,
                                                    dead):
    """THE per-row step on both of its routes, in both cache formats,
    against the scalar-position `decode_step` at equal positions, from
    a real prefilled cache written into pool blocks through the format.
    The oracle reads the cache as the format hands it back (int8 rounds
    it), so the dense view must agree with it exactly; the paged
    reduction is blockwise-online, and under int8 it also rounds the
    new row before it attends.  A dead row writes nothing."""
    cfg, params = model
    B, T, M, BS = 3, 6, 16, 4
    W, L = M // BS, cfg.n_layers
    prompt = jax.random.randint(jax.random.PRNGKey(5), (B, T), 0,
                                cfg.vocab_size, jnp.int32)
    logits, cache = llama.prefill(cfg, params, prompt, M)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    kv = _per_head_kv(cfg, BS, kv_dtype)
    tables = jnp.arange(1, 1 + B * W, dtype=jnp.int32).reshape(B, W)
    pool = kv.write(_empty_pool(kv, L, 1 + B * W), tables, cache)
    stored = kv.rows(pool, tables)
    l_ref, c_ref = llama.decode_step(cfg, params, tok, stored,
                                     jnp.asarray(T, jnp.int32))

    pos = jnp.full((B,), T, jnp.int32)
    live = jnp.asarray([b not in dead for b in range(B)])
    if route == "dense-view":
        l_got, view = llama.decode_step_rows(cfg, params, tok, stored, pos,
                                             live=live)
        pool2 = kv.write(pool, tables, view, span=(pos, pos + live))
        tol = 1e-5
    else:
        l_got, pool2 = llama.decode_step_rows(
            cfg, params, tok, pool, pos, tables=tables, live=live,
            interpret=True)
        assert len(pool2) == len(pool)  # back in the arity it came in
        tol = 2e-2 if kv_dtype == "model" else 5e-2
    rows = [b for b in range(B) if b not in dead]
    l_got, l_ref = np.asarray(l_got)[rows], np.asarray(l_ref)[rows]
    np.testing.assert_allclose(l_got, l_ref, rtol=tol, atol=tol)
    # the choice is the oracle's, or a tie inside the tolerance
    chosen = np.take_along_axis(l_ref, l_got.argmax(-1)[:, None], 1)[:, 0]
    assert np.all(l_ref.max(-1) - chosen <= (0 if tol == 1e-5 else tol))
    # the cache: a live row holds the oracle's new row, to the format's
    # rounding; nothing else moved, least of all a dead row's blocks
    after = kv.rows(pool2, tables)
    for got, want, before in zip(after, c_ref, stored):
        got, want = np.asarray(got), np.asarray(want)
        step = np.abs(want).max() / 127 if kv_dtype == "int8" else 0
        np.testing.assert_allclose(got[:, rows, T], want[:, rows, T],
                                   rtol=tol, atol=0.5 * step + tol)
        keep = np.ones(got.shape[:3], bool)
        keep[:, rows, T] = False
        np.testing.assert_array_equal(got[keep], np.asarray(before)[keep])
    for b in dead:
        for leaf, leaf2 in zip(pool, pool2):
            np.testing.assert_array_equal(
                np.asarray(leaf2[:, np.asarray(tables[b])]),
                np.asarray(leaf[:, np.asarray(tables[b])]))


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_paged_kv_write_then_view_round_trip(model, kv_dtype):
    """The format object alone, with a bf16 compute dtype so that a
    round trip through the view would re-round an int8 row.  Rows
    written into blocks come back exactly ("model") or within half a
    scale (int8); blocks not named keep every bit; and after a view is
    written back with a span, ONLY the span's rows were re-encoded."""
    cfg, _ = model
    L, B, n, BS = 2, 3, 2, 4
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    kv = _per_head_kv(cfg, BS, kv_dtype, dtype=jnp.bfloat16)
    rng = np.random.default_rng(11)
    pool = tuple(
        jnp.asarray(rng.integers(-100, 100, (L, 9, BS) + leaf.tail), leaf.dtype)
        if not leaf.sidecar else
        jnp.asarray(rng.uniform(0.01, 0.02, (L, 9, BS) + leaf.tail), leaf.dtype)
        for leaf in kv.leaves)
    blk = jnp.asarray([[1, 2], [5, 3], [7, 8]], jnp.int32)
    rows = [jnp.asarray(rng.standard_normal((L, B, n * BS, KV, hd)),
                        jnp.bfloat16) for _ in range(2)]
    pool2 = jax.jit(kv.write)(pool, blk, rows)
    back = kv.rows(pool2, blk)
    for got, want in zip(back, rows):
        assert got.dtype == jnp.bfloat16 and got.shape == want.shape
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        if kv_dtype == "model":
            np.testing.assert_array_equal(got, want)
        else:  # half a scale, and bf16's own rounding of the product
            scale = np.abs(want).max(-1, keepdims=True) / 127
            assert np.all(np.abs(got - want) <= 0.5 * scale + 2 ** -8
                          * np.abs(want))
    unnamed = np.asarray([0, 4, 6])
    for leaf, leaf2 in zip(pool, pool2):
        np.testing.assert_array_equal(np.asarray(leaf2[:, unnamed]),
                                      np.asarray(leaf[:, unnamed]))
    # a chunk's write-back: rows lo <= r < hi of the view were written.
    # The view is of the pool's first contents, whose int8 rows (no
    # payload at 127) a requantization would NOT give back
    lo, hi = jnp.asarray([1, 0, 5], jnp.int32), jnp.asarray([3, 0, 8], jnp.int32)
    inside = ((np.arange(n * BS)[None] >= np.asarray(lo)[:, None])
              & (np.arange(n * BS)[None] < np.asarray(hi)[:, None]))
    view = [jnp.where(inside[None, :, :, None, None], r, x)
            for r, x in zip(rows, kv.rows(pool, blk))]
    pool3 = jax.jit(lambda p, v: kv.write(p, blk, v, span=(lo, hi)))(
        pool, view)
    for leaf, leaf3, leaf2 in zip(pool, pool3, pool2):
        was, now, new = (np.asarray(jnp.take(x, blk, axis=1)).reshape(
            (L, B, n * BS) + x.shape[3:]) for x in (leaf, leaf3, leaf2))
        np.testing.assert_array_equal(now[:, ~inside], was[:, ~inside])
        np.testing.assert_array_equal(now[:, inside], new[:, inside])


def _run_engine(cfg, params, prompts, n_new, **kw):
    eng = LlamaEngine(cfg, params, slots=4, chunk=4, block_size=8,
                      max_len=64, kernel_interpret=True, **kw)
    try:
        outs = [f.result(timeout=120) for f in
                [eng.submit(p, n) for p, n in zip(prompts, n_new)]]
        return outs, eng.stats()
    finally:
        eng.shutdown()


@pytest.fixture(scope="module")
def workload(model):
    cfg, _ = model
    rng = np.random.RandomState(42)
    prompts, n_new = [], []
    for _ in range(7):  # > slots: queueing + slot reuse under kernel
        T = int(rng.randint(1, 24))
        prompts.append([int(x) for x in rng.randint(
            0, cfg.vocab_size, size=T)])
        n_new.append(int(rng.randint(1, 10)))
    return prompts, n_new


@pytest.mark.parametrize("dtype,prefix_cache", [
    ("bf16", True), ("bf16", False), ("fp32", True),
])
def test_engine_greedy_bit_identical_kernel_on_off(model, workload,
                                                   dtype, prefix_cache):
    """The acceptance gate: same greedy tokens with the kernel forced
    on vs the gather reference — at bf16 (the model default) AND
    fp32 — and the dispatch counters prove which plane actually ran
    each decode tick."""
    import dataclasses

    cfg, params = model
    if dtype == "fp32":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    prompts, n_new = workload
    on, s_on = _run_engine(cfg, params, prompts, n_new,
                           prefix_cache=prefix_cache,
                           decode_kernel="pallas")
    off, s_off = _run_engine(cfg, params, prompts, n_new,
                             prefix_cache=prefix_cache,
                             decode_kernel="gather")
    assert on == off
    assert s_on["decode_kernel"] == "pallas"
    assert s_on["decode_kernel_dispatch_total"] > 0
    assert s_on["decode_gather_dispatch_total"] == 0
    assert s_off["decode_kernel"] == "gather"
    assert s_off["decode_kernel_dispatch_total"] == 0
    assert s_off["decode_gather_dispatch_total"] > 0
    # and both routes match the dedicated-generate oracle
    for p, n, got in zip(prompts, n_new, on):
        assert got == _expected(cfg, params, p, n)


def test_engine_kernel_route_compiles_or_fails_to_start(model):
    """`decode_kernel="pallas"` means the COMPILED kernel: the engine
    runs a warm-up chunk through it in `__init__`, so where it cannot
    compile (here: a CPU backend, no interpret asked) the engine fails
    to start — it never serves through the gather route instead, and
    no engine thread is left behind."""
    import threading

    cfg, params = model
    before = {t.name for t in threading.enumerate()}
    with pytest.raises(Exception, match="(?i)interpret|cpu|pallas|mosaic"):
        LlamaEngine(cfg, params, slots=2, chunk=2, block_size=8,
                    max_len=32, decode_kernel="pallas")
    assert "llm-engine" not in (
        {t.name for t in threading.enumerate()} - before)


def test_engine_eviction_churned_pool_kernel_on(model):
    """Kernel correctness over a pool whose blocks have been freed and
    reallocated under budget pressure — block tables end up ragged and
    non-contiguous, the layout the kernel must not assume away."""
    cfg, params = model
    rng = np.random.RandomState(9)
    prompts = [[int(x) for x in rng.randint(0, cfg.vocab_size, size=12)]
               for _ in range(8)]
    eng = LlamaEngine(cfg, params, slots=2, chunk=2, block_size=8,
                      max_len=32, kv_blocks=10, prefix_cache=False,
                      decode_kernel="pallas", kernel_interpret=True)
    try:
        futs = [eng.submit(p, 6) for p in prompts]
        outs = [f.result(timeout=120) for f in futs]
        assert eng.stats()["decode_kernel_dispatch_total"] > 0
    finally:
        eng.shutdown()
    for p, got in zip(prompts, outs):
        assert got == _expected(cfg, params, p, 6)


def test_engine_int8_kv_pallas_equals_gather(model, workload):
    """Int8 KV numerics gate: the fused-dequant kernel and the
    dequantize-then-gather fallback see the SAME stored payload, so
    their greedy outputs must agree exactly; vs the fp oracle the
    quantized engine is argmax-gated, not bit-gated."""
    cfg, params = model
    prompts, n_new = workload
    q_on, s_on = _run_engine(cfg, params, prompts, n_new,
                             kv_dtype="int8", decode_kernel="pallas")
    q_off, s_off = _run_engine(cfg, params, prompts, n_new,
                               kv_dtype="int8", decode_kernel="gather")
    assert q_on == q_off
    assert s_on["kv_dtype"] == "int8"
    assert s_on["decode_kernel_dispatch_total"] > 0
    assert s_off["decode_gather_dispatch_total"] > 0
    # documented tolerance: >= 70% of requests reproduce the fp greedy
    # tokens end-to-end (int8 KV error can flip a near-tie argmax)
    matches = sum(
        got == _expected(cfg, params, p, n)
        for p, n, got in zip(prompts, n_new, q_on)
    )
    assert matches >= int(0.7 * len(prompts)), (
        f"int8 KV argmax match {matches}/{len(prompts)}"
    )


def test_engine_int8_pool_half_bytes(model, workload):
    """At the same block budget the int8 pool's payload is exactly
    half the bf16 pool's, with the f32 scale sidecar priced
    separately in stats()."""
    cfg, params = model
    prompts, n_new = workload
    _, s_fp = _run_engine(cfg, params, prompts[:2], n_new[:2],
                          kv_blocks=32)
    _, s_q = _run_engine(cfg, params, prompts[:2], n_new[:2],
                         kv_blocks=32, kv_dtype="int8")
    assert s_fp["kv_dtype"] == "model" and s_fp["kv_scale_bytes"] == 0
    assert s_q["kv_pool_bytes"] * 2 == s_fp["kv_pool_bytes"]
    assert s_q["kv_scale_bytes"] > 0


def test_int8_weights_bounded_error_and_engine_parity(model):
    """`quantize_weights_int8`: per-output-channel scales keep the
    forward logits within ~5% of fp, and the int8 choice sits within
    that bound of the float choice under the float logits (a margin,
    as the benchmark's `correct` compares: a near tie may flip);
    the engine serving the quantized params reproduces the dedicated
    `generate` over the same quantized params exactly."""
    cfg, params = model
    qparams = llama.quantize_weights_int8(params)
    assert qparams["blocks"]["wq"].dtype == jnp.int8
    assert qparams["blocks"]["wq_scale"].shape == (
        cfg.n_layers, cfg.n_heads * cfg.head_dim)
    assert qparams["lm_head"].dtype == jnp.int8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 10), 0,
                                cfg.vocab_size, jnp.int32)
    lf = np.asarray(llama.forward(cfg, params, tokens), np.float32)
    lq = np.asarray(llama.forward(cfg, qparams, tokens), np.float32)
    scale = np.abs(lf).max()
    assert np.abs(lq - lf).max() <= 0.05 * scale, (
        f"int8 weight logit error {np.abs(lq - lf).max():.4f} "
        f"vs scale {scale:.4f}"
    )
    chosen = np.take_along_axis(lf, np.argmax(lq, -1)[..., None], -1)[..., 0]
    assert (lf.max(-1) - chosen).max() <= 0.05 * scale

    rng = np.random.RandomState(17)
    prompts = [[int(x) for x in rng.randint(0, cfg.vocab_size, size=8)]
               for _ in range(3)]
    outs, _ = _run_engine(cfg, qparams, prompts, [6] * 3,
                          decode_kernel="pallas")
    for p, got in zip(prompts, outs):
        assert got == _expected(cfg, qparams, p, 6)


def test_chunk_cache_lru_caps_and_counts_evictions(model):
    """The per-width compiled-chunk cache is LRU-bounded: building a
    third width under cap=2 evicts the least-recently-used entry and
    the counters surface in stats()."""
    cfg, params = model
    eng = LlamaEngine(cfg, params, slots=2, chunk=2, block_size=8,
                      max_len=32, chunk_cache_cap=2)
    try:
        eng._chunk_step_for(1)
        eng._chunk_step_for(2)
        eng._chunk_step_for(1)  # refresh width 1 -> width 2 is LRU
        eng._chunk_step_for(3)  # evicts width 2
        assert set(eng._chunk_cache) == {1, 3}
        eng._chunk_step_for(2)  # rebuild: evicts width 1
        assert set(eng._chunk_cache) == {3, 2}
        s = eng.stats()
        assert s["chunk_cache_size"] == 2
        assert s["chunk_cache_evictions"] == 2
    finally:
        eng.shutdown()


def test_engine_config_and_schema_validation():
    from ray_tpu.serve.schema import LLMEngineSchema

    with pytest.raises(ValueError):
        LLMEngineConfig(decode_kernel="vulkan").validate()
    with pytest.raises(ValueError):
        LLMEngineConfig(kv_dtype="fp8").validate()
    with pytest.raises(ValueError):
        LLMEngineSchema.model_validate({"weight_dtype": "int4"})
    with pytest.raises(ValueError):
        LLMEngineSchema.model_validate({"chunk_cache_cap": 0})
    cfg = LLMEngineSchema.model_validate(
        {"decode_kernel": "gather", "kv_dtype": "int8", "slots": 2}
    ).to_config()
    kw = cfg.engine_kwargs()
    assert kw["decode_kernel"] == "gather"
    assert kw["kv_dtype"] == "int8"
    assert "weight_dtype" not in kw  # applied to params pre-engine
