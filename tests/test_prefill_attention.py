"""`ops/prefill_attention.py` (a full layer's prefill attention as one
fused kernel: grouped queries against contiguous keys behind a start
position, under a segment and a causal mask, key blocks walked inside
the kernel) in the Pallas interpreter on the CPU, against ONE softmax
over the whole context (`models/mimo_v2._attend`), at small shapes with
the cell's ratios: 2 key/value heads x 4 query heads a group, keys 48 /
values 32 wide (once 192 / 128), query blocks of 8 rows against key
blocks of 16."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import mimo_v2
from ray_tpu.ops.prefill_attention import block_walk, prefill_attention

KV, G = 2, 4
BQ, BK = 8, 16


def segments(*runs):
    """`(segment, rows)` runs end to end -> int32 [sum of rows]."""
    return np.concatenate([np.full(n, s, np.int32) for s, n in runs])


def chunk(N, S, lo, n):
    """One sequence: `n` real rows of `N` from key row `lo` on."""
    return dict(qseg=segments((0, n), (-1, N - n)), kseg=np.zeros(S, np.int32),
                lo=lo)


def packed(*runs):
    """Prompts packed end to end, the row its own keys."""
    seg = segments(*runs)
    return dict(qseg=seg, kseg=seg, lo=0)


CASES = {
    "lo-0": chunk(32, 32, 0, 32),
    "lo-one-key-block": chunk(32, 64, 16, 32),
    "lo-several-blocks-and-a-part": chunk(32, 96, 40, 32),
    "n-short-of-N": chunk(32, 96, 40, 27),
    "a-query-block-of-padding-alone": chunk(32, 96, 32, 11),
    "context-ends-inside-a-key-block": chunk(16, 64, 24, 13),
    "one-key-block": chunk(8, 16, 0, 8),
    "rows-that-are-no-whole-blocks": chunk(20, 43, 23, 20),
    "two-packed-and-padding": packed((0, 19), (-1, 5), (1, 17), (-1, 7)),
    "three-packed-and-padding": packed((0, 19), (-1, 5), (1, 3), (-1, 5),
                                       (2, 31), (-1, 9)),
    "a-prompt-longer-than-the-blocks-before-it": packed((0, 3), (-1, 5),
                                                        (1, 56)),
    "behind-another-prompts-rows": dict(
        qseg=segments((1, 17), (-1, 7)),
        kseg=segments((0, 19), (-1, 5), (1, 17), (-1, 7)), lo=24),
    "rows-that-see-no-key": dict(
        qseg=segments((0, 8), (5, 6), (0, 10), (-1, 8)),
        kseg=segments((0, 24), (-1, 8)), lo=0),
    "segments-in-no-order": dict(
        qseg=np.asarray([1, 0, 2, 1, -1, 0, 2, 2] * 4, np.int32),
        kseg=np.asarray([2, 0, 1, 1, 0, -1, 2, 0] * 6, np.int32), lo=13),
    "keys-192-values-128": dict(chunk(16, 48, 24, 14), dk=192, dv=128),
    "bfloat16": dict(chunk(32, 96, 40, 27), dtype=jnp.bfloat16),
}


def visible(qseg, kseg, lo):
    i = np.arange(len(qseg))[:, None]
    j = np.arange(len(kseg))[None, :]
    return (kseg[None, :] == qseg[:, None]) & (j <= lo + i) \
        & (qseg[:, None] >= 0)


def operands(case, seed=0):
    dk, dv = case.get("dk", 48), case.get("dv", 32)
    dt = case.get("dtype", jnp.float32)
    N, S = len(case["qseg"]), len(case["kseg"])
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(key[0], (N, KV, G, dk)).astype(dt),
            jax.random.normal(key[1], (S, KV, dk)).astype(dt),
            jax.random.normal(key[2], (S, KV, dv)).astype(dt))


def fused(case, q, k, v):
    return np.asarray(prefill_attention(
        q, k, v, jnp.asarray(case["qseg"]), jnp.asarray(case["kseg"]),
        jnp.int32(case["lo"]), scale=q.shape[-1] ** -0.5, block_q=BQ,
        block_k=BK, interpret=True).astype(jnp.float32))


@pytest.mark.parametrize("name", list(CASES))
def test_the_fused_fold_equals_one_softmax_over_the_context(name):
    """Every row against the dense softmax under the same mask; a row
    that sees no key (padding, a segment no key has) comes back ZERO,
    never NaN, whatever its block's other rows see."""
    case = CASES[name]
    q, k, v = operands(case)
    mask = visible(case["qseg"], case["kseg"], case["lo"])
    cfg = dataclasses.replace(mimo_v2.MimoV2Config.tiny(),
                              head_dim=q.shape[-1], dtype=q.dtype)
    want = np.asarray(mimo_v2._attend(cfg, q, k, v, jnp.asarray(mask), None))
    want = np.where(mask.any(axis=1)[:, None, None, None], want, 0.0)
    got = fused(case, q, k, v)
    assert got.shape == want.shape and np.isfinite(got).all()
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 2e-5
    assert np.abs(got - want).max() < tol
    assert np.abs(want).max() > 0.1
    dead = ~mask.any(axis=1)
    assert not got[dead].any()


def _walk(case):
    """The case's walk as the kernel takes it (rows padded to whole
    blocks as `prefill_attention` pads them) and the mask by block
    pair `[nq, nk, BQ, BK]`."""
    qseg, kseg = case["qseg"], case["kseg"]
    qseg = np.pad(qseg, (0, -len(qseg) % BQ), constant_values=-1)
    kseg = np.pad(np.where(kseg < 0, -2, kseg), (0, -len(kseg) % BK),
                  constant_values=-2)
    walk = [np.asarray(x) for x in block_walk(
        jnp.asarray(qseg), jnp.asarray(kseg), jnp.int32(case["lo"]), BQ, BK)]
    mask = visible(qseg, kseg, case["lo"])
    nq, nk = len(qseg) // BQ, len(kseg) // BK
    return walk, mask.reshape(nq, BQ, nk, BK).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("name", list(CASES))
def test_the_walk_skips_what_no_row_sees_and_masks_the_rest(name):
    """The kernel's loop bounds by query block: every block pair with a
    visible (query, key) is walked, a pair wholly above the diagonal or
    past the last real row is not, and a block walked without a mask is
    one every row sees whole."""
    (start, plain_end, end), mask = _walk(CASES[name])
    nq, nk = mask.shape[:2]
    assert ((0 <= start) & (start <= plain_end) & (plain_end <= end)
            & (end <= nk)).all()
    for b in range(nq):
        for j in range(nk):
            walked = start[b] <= j < end[b]
            if mask[b, j].any():
                assert walked, (b, j)
            if walked and j < plain_end[b]:
                assert mask[b, j].all(), (b, j)
        # nothing past the diagonal or the context's end is fetched
        seen = np.flatnonzero(mask[b].any(axis=(1, 2)))
        assert end[b] == (seen[-1] + 1 if len(seen) else 0)


@pytest.mark.parametrize("name,fetched,of,unread", [
    # a 32-row chunk at lo 0: query blocks of 8 against key blocks of
    # 16 walk 1, 1, 2, 2 of the chunk's own 2 blocks
    ("lo-0", 6, 8, 0),
    # ... and behind 40 rows: 3, 4, 4, 5 of 6, the table's last never
    ("lo-several-blocks-and-a-part", 16, 24, 1),
    # 27 real rows of 32: the last block's 3 real rows end the context
    ("n-short-of-N", 16, 24, 1),
    # 11 real rows: the third and fourth query blocks fetch nothing
    ("a-query-block-of-padding-alone", 6, 24, 3),
    # the second prompt's query blocks skip the first prompt's block
    ("two-packed-and-padding", 9, 18, 0),
    ("behind-another-prompts-rows", 5, 9, 1),
])
def test_key_blocks_fetched(name, fetched, of, unread):
    """The key blocks the kernel copies in (one DMA pair a loop trip,
    `end - start` trips a query block), of the grid a walk over every
    pair would take; and what it skips it does not READ: with the keys
    and values of the `unread` blocks no query block walks NaN, the
    result is the same."""
    case = CASES[name]
    (start, _, end), mask = _walk(case)
    nq, nk = mask.shape[:2]
    assert (int((end - start).sum()), nq * nk) == (fetched, of)
    q, k, v = operands(case)
    skipped = np.ones(nk, bool)
    for b in range(nq):
        skipped[start[b]:end[b]] = False
    assert skipped.sum() == unread
    rows = jnp.asarray(np.repeat(skipped, BK)[:k.shape[0]])[:, None, None]
    assert np.array_equal(
        fused(case, q, jnp.where(rows, jnp.nan, k),
              jnp.where(rows, jnp.nan, v)),
        fused(case, q, k, v))
