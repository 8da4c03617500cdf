"""`models/dots3.py` (latent attention of two forms, a learned sparse
selection, a share of an expert layer) against the plain float32
reference `benchmarks/reference/dots3.py`, at tiny widths on the CPU:
the whole forward, prefill in chunks + a suffix behind a cached prefix
+ decoding through the paged cache, the expert layer's shares, the
window's reach, and the engine end to end."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import dots3 as ref
from ray_tpu.models import deepseek_v3, dots3, llama
from ray_tpu.parallel import moe
from ray_tpu.serve.engine_model import engine_model_for
from ray_tpu.serve.llm_engine import LlamaEngine

CFG = dots3.Dots3Config.tiny()
BS = 8
# float32 against float32 with sums in another order; a bfloat16 model
# against the same reference reads 100 x this (test_bf16_breaks_it)
TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return dots3.init_params(CFG, jax.random.PRNGKey(7), std=0.2)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, size=n)


def ref_kwargs(cfg, i):
    kind = cfg.layer_types[i]
    a = dots3.attn_form(cfg, kind)
    kw = dict(kind=kind, eps=cfg.norm_eps, top_k=cfg.top_k,
              scale=cfg.routed_scale, offset=cfg.expert_offset,
              attn=dict(heads=a.heads, nope=a.nope, rope=a.rope, v_dim=a.v,
                        rank=a.rank, theta=a.theta, s_q=a.s_q, s_kv=a.s_kv))
    if kind == dots3.FULL:
        kw["index"] = (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk)
    else:
        kw["window"] = cfg.window
    return kw


def ref_logits(cfg, params, toks, **kw):
    x = ref.embed(jnp.asarray(toks), params["tok_emb"])
    overlaps = []
    for i, layer in enumerate(params["layers"]):
        x, ov = ref.layer(x, layer, **ref_kwargs(cfg, i), qblock=8,
                          hgroup=2, **kw)
        overlaps.append(float(ov))
    return np.asarray(ref.head(x, params["final_norm"], params["lm_head"],
                               cfg.norm_eps)), overlaps


_DECODE = jax.jit(
    lambda p, c, t, q, tb: dots3.decode_step(CFG, p, t, c, q, tb))
_PREFILL = jax.jit(
    lambda p, t, q, c, tb: dots3.forward_with_prefix(CFG, p, t, q, c, tb))


class Cache:
    """One sequence's paged cache, in the leaves the engine model names,
    driven through the model's two functions as the engine's programs
    drive them."""

    def __init__(self, cfg, params, blocks=24):
        self.cfg, self.params = cfg, params
        em = engine_model_for(cfg, kv_dtype="model", block_size=BS,
                              chunk=1, paged=False, interpret=False)
        self.cache = tuple(
            jnp.zeros((leaf.layers, blocks + 1, BS) + leaf.tail, leaf.dtype)
            for leaf in em.cache_leaves)
        self.table = list(range(1, blocks + 1))

    def prefill(self, toks, lo, hi, bucket=None):
        """Tokens lo..hi behind the cached 0..lo, a pack of one in a
        program of `bucket` rows; returns their logits."""
        S = hi - lo
        bucket = bucket or -(-S // BS) * BS
        posn = np.full(bucket, -1, np.int32)
        posn[:S] = np.arange(lo, hi)
        suffix = np.zeros(bucket, np.int32)
        suffix[:S] = toks[lo:hi]
        logits, self.cache, _ = _PREFILL(
            self.params, jnp.asarray(suffix), jnp.asarray(posn), self.cache,
            jnp.asarray([self.table], jnp.int32))
        return np.asarray(logits[:S])

    def decode(self, tok, pos, width=None):
        tables = jnp.asarray([self.table[:width or len(self.table)]],
                             jnp.int32)
        logits, self.cache, stats = _DECODE(self.params, self.cache, jnp.asarray([tok], jnp.int32),
          jnp.asarray([pos], jnp.int32), tables)
        return np.asarray(logits[0]), stats


# ----------------------------------------------------------------------
def test_the_tiny_contexts_bind_both_mechanisms():
    assert 40 > CFG.index_topk and 40 > CFG.window
    assert CFG.layer_types.count(dots3.SWA) == 3
    assert dots3.attn_form(CFG, dots3.FULL).heads != \
        dots3.attn_form(CFG, dots3.SWA).heads


def test_full_forward_equals_the_reference(params):
    toks = tokens(40)
    want, _ = ref_logits(CFG, params, toks)
    got = np.asarray(dots3.forward(CFG, params, jnp.asarray(toks)))
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).mean() > 0.3   # logits of order one


def test_full_forward_with_the_held_pairs_compacted_equals_the_reference(
        params, monkeypatch):
    """`parallel/moe.COMPACT_FROM` lowered to this model's size: the
    expert layers move the held pairs alone, the logits stay the
    reference's."""
    monkeypatch.setattr(moe, "COMPACT_FROM", 64)
    slabs, traced = moe._held_slabs, []
    monkeypatch.setattr(moe, "_held_slabs", lambda *a, **kw: (
        traced.append(kw["rows"]), slabs(*a, **kw))[1])
    toks = tokens(40)
    assert moe.slab_rows(40 * CFG.top_k, CFG.experts_held,
                         CFG.n_routed_experts)
    want, _ = ref_logits(CFG, params, toks)
    got = np.asarray(dots3.forward(CFG, params, jnp.asarray(toks)))
    assert np.abs(got - want).max() < TOL
    assert traced and set(traced) == {128}


def test_bf16_breaks_it(params):
    """The tolerance is one a bfloat16-for-float32 swap breaks."""
    toks = tokens(40)
    want, _ = ref_logits(CFG, params, toks)
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    low = jax.tree.map(lambda v: v.astype(jnp.bfloat16)
                       if v.dtype == jnp.float32 and v.ndim > 1 else v, params)
    got = np.asarray(dots3.forward(cfg, low, jnp.asarray(toks)))
    assert np.abs(got - want).max() > 20 * TOL


@pytest.mark.parametrize("chunks", [
    [(0, 40)], [(0, 16), (16, 32), (32, 40)], [(0, 8), (8, 40)]])
def test_prefill_in_chunks_equals_the_reference(params, chunks):
    """(a), (d): the prompt in one program or in chunks behind its own
    blocks: the same logits, the reference's."""
    toks = tokens(40)
    want, _ = ref_logits(CFG, params, toks)
    c = Cache(CFG, params)
    got = np.concatenate([c.prefill(toks, lo, hi) for lo, hi in chunks])
    assert np.abs(got - want).max() < TOL


def test_suffix_behind_a_hit_then_decode_equals_the_reference(params):
    """(a): a prompt's first 32 tokens are a cached prefix (another
    request's blocks); the suffix is prefilled behind them right-padded
    to its bucket, then 12 tokens decode through the cache, teacher-
    forced: every logit the reference's."""
    toks = tokens(56, seed=3)
    want, _ = ref_logits(CFG, params, toks)
    c = Cache(CFG, params)
    c.prefill(toks, 0, 32)                          # the cached prefix
    got = c.prefill(toks, 32, 37, bucket=8)         # the hit's suffix
    assert np.abs(got - want[32:37]).max() < TOL
    for pos in range(37, 50):
        lg, stats = c.decode(int(toks[pos]), pos)
        assert np.abs(lg - want[pos]).max() < TOL, pos
    assert int(stats["experts_touched"]) <= 4 * CFG.experts_held


def test_a_narrower_table_reads_the_same(params):
    """A decode step at a table as wide as the context needs equals one
    at the whole table (the engine's gather width only grows)."""
    toks = tokens(30, seed=5)
    a, b = Cache(CFG, params), Cache(CFG, params)
    for c in (a, b):
        c.prefill(toks, 0, 24)
    la, _ = a.decode(int(toks[24]), 24, width=4)
    lb, _ = b.decode(int(toks[24]), 24)
    assert np.abs(la - lb).max() < 1e-5


def test_window_decode_reads_no_row_older_than_the_window(params):
    """(c): the window layers' rows older than the window poisoned in
    the pool: a decode step's logits do not move.  A row INSIDE the
    window poisoned: they do."""
    toks = tokens(40, seed=9)
    clean, dirty, inside = (Cache(CFG, params) for _ in range(3))
    for c in (clean, dirty, inside):
        c.prefill(toks, 0, 36)
    pos = 36

    def poison(c, positions):
        swa = c.cache[2]
        for p in positions:
            swa = swa.at[:, c.table[p // BS], p % BS].set(1e4)
        c.cache = (*c.cache[:2], swa)

    poison(dirty, range(0, pos - CFG.window + 1))   # 0 .. 26: too old
    poison(inside, [pos - CFG.window + 1])          # 27: the oldest inside
    want, _ = clean.decode(int(toks[pos]), pos)
    got, _ = dirty.decode(int(toks[pos]), pos)
    assert np.array_equal(got, want)
    moved, _ = inside.decode(int(toks[pos]), pos)
    assert not np.allclose(moved, want, atol=1e-3)


def test_the_window_edge_counts_the_token_itself():
    m = np.asarray(ref.window_mask(12, 5))
    assert m[8].nonzero()[0].tolist() == [4, 5, 6, 7, 8]


@pytest.mark.parametrize("k", [64, 1000])
def test_a_selection_of_everything_is_dense_latent_attention(k):
    """(e): `k >= T` keeps every live row: the selected attention
    equals the dense absorbed form `deepseek_v3` decodes with."""
    a = dots3.attn_form(CFG, dots3.FULL)
    dcfg = deepseek_v3.DeepseekV3Config(
        dim=CFG.dim, n_heads=a.heads, qk_nope_dim=a.nope, qk_rope_dim=a.rope,
        v_head_dim=a.v, kv_lora_rank=a.rank, dtype=jnp.float32)
    B, M = 3, 40
    kq, kr, ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (B, a.heads, a.latent))
    rows = jax.random.normal(kr, (B, M, a.latent))
    pos = jnp.asarray([5, 39, 17])
    want = deepseek_v3._absorbed_dense(dcfg, q, rows, pos)
    sc = jnp.where(jnp.arange(M)[None] <= pos[:, None],
                   jax.random.normal(ks, (B, M)), dots3.NEG)
    idx, real = dots3._select(sc, k)
    got = dots3._attend_rows(
        a, q, jnp.take_along_axis(rows, idx[..., None], axis=1), real,
        jnp.float32)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_selection_off_is_another_result(params):
    """The selection binds at 40 tokens of context: with it off
    (`index_topk` past the context) the logits move."""
    toks = tokens(40)
    on = np.asarray(dots3.forward(CFG, params, jnp.asarray(toks)))
    off = np.asarray(dots3.forward(
        dataclasses.replace(CFG, index_topk=4096), params, jnp.asarray(toks)))
    assert np.abs(on[:CFG.index_topk] - off[:CFG.index_topk]).max() < 1e-5
    assert np.abs(on[-1] - off[-1]).max() > 1e-3


def test_index_select_overlap_is_reported(params):
    toks = tokens(40)
    _, overlaps = ref_logits(CFG, params, toks, overlap_rows=(32, 8))
    full = [o for o, t in zip(overlaps, CFG.layer_types) if t == dots3.FULL]
    swa = [o for o, t in zip(overlaps, CFG.layer_types) if t == dots3.SWA]
    assert all(0.8 < o <= 1.0 for o in full) and all(map(math.isnan, swa))


# -- (b) the share ------------------------------------------------------
def _moe_layer(E=16, D=32, I=16, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 8)
    n = lambda k, s: jax.random.normal(k, s) * 0.3  # noqa: E731
    return {"router": n(ks[0], (D, E)), "router_bias": n(ks[1], (E,)) * 0.1,
            "e_gate": n(ks[2], (E, D, I)), "e_up": n(ks[3], (E, D, I)),
            "e_down": n(ks[4], (E, I, D)), "s_gate": n(ks[5], (D, I)),
            "s_up": n(ks[6], (D, I)), "s_down": n(ks[7], (I, D))}


def _share(layer, lo, n):
    return {**layer, **{k: layer[k][lo:lo + n]
                        for k in ("e_gate", "e_up", "e_down")}}


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The shares' routed parts plus the shared expert counted once
    equal the reference's uncut layer; each share's part equals the
    reference's of that share."""
    layer, E = _moe_layer(), 16
    h = jax.random.normal(jax.random.PRNGKey(5), (24, 32))
    kw = dict(top_k=4, scale=1.0, quant=ref._identity)
    uncut = ref.routed(h, layer, offset=0, **kw) + ref.shared(h, layer)
    n = E // shares
    total = ref.shared(h, layer)
    for s in range(shares):
        part, stats = moe.dropless_moe(
            h, _share(layer, s * n, n), top_k=4, scale=1.0, route_eps=1e-20,
            dtype=jnp.float32, held=(s * n, n))
        want = ref.routed(h, _share(layer, s * n, n), offset=s * n, **kw)
        assert np.abs(np.asarray(part) - np.asarray(want)).max() < 1e-5
        assert int(stats["experts_touched"]) <= n
        total = total + part
    assert np.abs(np.asarray(total) - np.asarray(uncut)).max() < 1e-5


def test_holding_every_expert_is_the_layer_as_it_was():
    layer = _moe_layer()
    h = jax.random.normal(jax.random.PRNGKey(6), (10, 32))
    kw = dict(top_k=4, scale=1.0, route_eps=1e-20, dtype=jnp.float32)
    a, sa = moe.dropless_moe(h, layer, **kw)
    b, sb = moe.dropless_moe(h, layer, held=(0, 16), **kw)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert {k: int(v) for k, v in sa.items()} == \
        {k: int(v) for k, v in sb.items()}


def test_a_masked_row_of_a_share_is_zeros():
    layer = _share(_moe_layer(), 4, 4)
    h = jax.random.normal(jax.random.PRNGKey(8), (6, 32))
    mask = jnp.asarray([True, False, True, True, False, True])
    y, stats = moe.dropless_moe(
        h, layer, top_k=4, scale=1.0, route_eps=1e-20, dtype=jnp.float32,
        held=(4, 4), row_mask=mask)
    assert not np.asarray(y)[[1, 4]].any()
    assert np.isfinite(np.asarray(y)).all()


# -- the engine ---------------------------------------------------------
_FORWARD = jax.jit(lambda p, t: dots3.forward(CFG, p, t))


def _greedy(cfg, params, prompt, n, width=64):
    """The greedy loop over the whole forward, right-padded to one
    width (padding changes no real token's result): one program."""
    assert cfg is CFG
    seq = list(prompt)
    for _ in range(n):
        lg = _FORWARD(params, jnp.asarray(seq + [0] * (width - len(seq))))
        seq.append(int(jnp.argmax(lg[len(seq) - 1])))
    return seq[len(prompt):]


@pytest.fixture(scope="module")
def engine(params):
    eng = LlamaEngine(CFG, params, slots=3, chunk=2, block_size=BS,
                      max_len=96, kv_blocks=40, prefill_chunk=16)
    yield eng
    eng.shutdown()


def test_engine_chunked_admission_hit_and_decode(engine, params):
    """A prompt past the chunk is admitted in three chunks; a second
    prompt sharing its first 32 tokens is a prefix HIT of 32 and one
    chunk; both answers are the greedy loop's over the whole forward."""
    prompt = tokens(37, seed=11).tolist()
    out = engine.submit(prompt, 12).result(timeout=600)
    assert out == _greedy(CFG, params, prompt, 12)
    other = prompt[:33] + [5, 6, 7]
    out2 = engine.submit(other, 10).result(timeout=600)
    assert out2 == _greedy(CFG, params, other, 10)
    st = engine.stats()
    first, second = st["request_ring"][-2:]
    assert (first["prefill_chunks"], first["tokens_hit"]) == (3, 0)
    assert (second["prefill_chunks"], second["tokens_hit"]) == (1, 32)
    assert st["prefix_hit_tokens"] == 32
    assert st["prefill_tokens"] == 37 + 4
    assert st["cache_bytes_per_token"] == 4 * (
        2 * (CFG.latent_dim + CFG.index_head_dim) + 3 * CFG.swa_latent_dim)
    assert st["cache_bytes_per_slot"] == 0


def test_engine_tick_fields(engine):
    fut = engine.submit(tokens(20, seed=13).tolist(), 8)
    fut.result(timeout=600)
    ticks = [t for t in engine.stats()["tick_ring"] if t["row_steps_live"]]
    t = ticks[-1]
    assert t["experts_held"] == CFG.experts_held
    assert t["experts_total"] == 4 * CFG.experts_held
    assert 0.0 < t["dsa_selected_share"] <= 1.0
    assert 0 < t["window_rows_live"] <= CFG.window
    # a context of 21 tokens: 12 of them selected, 10 in the window
    assert any(abs(x["dsa_selected_share"] - 12 / 21) < 1e-9
               and x["window_rows_live"] == 10 for x in ticks)
    hit = [x for x in engine.stats()["tick_ring"]
           if x.get("prefix_hit_tokens")]
    assert hit and all(x["prefill_tokens"] > 0 for x in hit)
    assert sum(x["prefix_hit_tokens"] for x in hit) == \
        engine.stats()["prefix_hit_tokens"]


def test_engine_concurrent_rows_keep_their_own_windows(engine, params):
    prompts = [tokens(n, seed=20 + n).tolist() for n in (9, 30, 45)]
    futs = [engine.submit(p, 9) for p in prompts]
    for p, f in zip(prompts, futs):
        assert f.result(timeout=600) == _greedy(CFG, params, p, 9)


def test_a_model_without_packs_needs_no_chunk(params):
    eng = LlamaEngine(CFG, params, slots=2, chunk=2, block_size=BS,
                      max_len=64, kv_blocks=20)
    try:
        prompt = tokens(21, seed=31).tolist()
        assert eng.submit(prompt, 5).result(timeout=600) == \
            _greedy(CFG, params, prompt, 5)
        assert eng.stats()["request_ring"][-1]["prefill_chunks"] == 1
    finally:
        eng.shutdown()


def test_prefill_chunk_is_whole_blocks(params):
    with pytest.raises(ValueError, match="whole blocks"):
        LlamaEngine(CFG, params, slots=2, chunk=2, block_size=BS,
                    max_len=64, kv_blocks=20, prefill_chunk=12)


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_a_dense_model_takes_prompts_past_its_largest_pack(prefix_cache):
    """`prefill_chunk` on a model WITH a packed prefill: the pack sizes
    stop at the chunk, a longer prompt goes chunk by chunk behind its
    own blocks, and the tokens are those of the engine without it."""
    cfg = llama.LlamaConfig.tiny()
    p = llama.init_params(cfg, jax.random.PRNGKey(2))
    kw = dict(slots=2, chunk=2, block_size=8, max_len=96,
              prefix_cache=prefix_cache)
    prompts = [np.random.default_rng(s).integers(1, cfg.vocab_size, size=n)
               .tolist() for s, n in ((1, 70), (2, 12), (3, 33))]
    plain = LlamaEngine(cfg, p, **kw)
    chunked = LlamaEngine(cfg, p, prefill_chunk=32, **kw)
    try:
        assert chunked._pack_sizes[-1] == 32
        for prompt in prompts:
            a = plain.submit(prompt, 6).result(timeout=600)
            b = chunked.submit(prompt, 6).result(timeout=600)
            assert a == b
        ring = chunked.stats()["request_ring"]
        assert [r["prefill_chunks"] for r in ring[-3:]] == [3, 0, 2]
    finally:
        plain.shutdown()
        chunked.shutdown()
