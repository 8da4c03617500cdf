"""`models/mimo_v2.py` (window layers with a learned sink on a per-slot
ring beside paged full layers of other head counts, keys 192 and values
128 wide, a share of an expert layer) against the plain float32
reference `benchmarks/reference/mimo_v2.py`, at tiny widths on the CPU:
the whole forward, packed prefill + decoding through both caches past
the ring's wrap, chunked admission, the sink, the expert layer's shares,
and the engine end to end."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mimo_v2 as ref
from ray_tpu.exceptions import PrefixCacheUnsupportedError
from ray_tpu.models import brumby, lfm2, mimo_v2
from ray_tpu.models.llama import Packed
from ray_tpu.parallel import moe
from ray_tpu.serve.engine_model import engine_model_for
from ray_tpu.serve.kv_cache import BlockPool
from ray_tpu.serve.llm_engine import LlamaEngine

CFG = mimo_v2.MimoV2Config.tiny()
BS = 8
# float32 against float32 with sums in another order (a running softmax
# against a dense one, a grouped product against experts one at a
# time); a bfloat16 model against the same reference reads 100 x this
# (test_bf16_breaks_it)
TOL = 2e-4
# past the ring's wrap: at least 3 x window + chunk
LONG = 3 * CFG.window + 16


@pytest.fixture(scope="module")
def params():
    return mimo_v2.init_params(CFG, jax.random.PRNGKey(7), std=0.2)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, size=n)


def published_keys(cfg):
    """The config as the published keys the reference reads."""
    return {
        "hybrid_layer_pattern": list(cfg.layer_pattern),
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "swa_num_attention_heads": cfg.swa_n_heads,
        "swa_num_key_value_heads": cfg.swa_n_kv_heads,
        "head_dim": cfg.head_dim, "swa_head_dim": cfg.head_dim,
        "v_head_dim": cfg.v_head_dim, "swa_v_head_dim": cfg.v_head_dim,
        "partial_rotary_factor": (cfg.rotary_dim + 0.5) / cfg.head_dim,
        "rope_theta": cfg.rope_theta, "swa_rope_theta": cfg.swa_rope_theta,
        "attention_value_scale": cfg.value_scale,
        "sliding_window": cfg.window,
        "add_swa_attention_sink_bias": cfg.swa_sink,
        "add_full_attention_sink_bias": cfg.full_sink,
        "layernorm_epsilon": cfg.norm_eps,
        "num_experts_per_tok": cfg.top_k, "routed_scaling_factor": None}


def ref_logits(cfg, params, toks, offset=None):
    m = published_keys(cfg)
    offset = cfg.expert_offset if offset is None else offset
    with jax.default_matmul_precision("highest"):
        x = ref.embed(jnp.asarray(toks), params["tok_emb"])
        for l, w in enumerate(params["layers"]):
            x = ref.layer(x, w, qblock=len(toks),
                          **ref.layer_kwargs(m, l, offset=offset))
        return np.asarray(ref.head(x, params["final_norm"],
                                   params["lm_head"], cfg.norm_eps))


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(lambda p, t: mimo_v2.forward(cfg, p, t)[0][0])


def forward_logits(cfg, params, toks):
    return np.asarray(_forward(cfg)(params, jnp.asarray([toks], jnp.int32)))


@functools.lru_cache(maxsize=None)
def _packed(cfg, paged=False):
    return jax.jit(lambda p, t, ring, packed, slots: mimo_v2.forward(
        cfg, p, t, ring, packed=packed, slots=slots, paged_kernel=paged,
        interpret=paged))


@functools.lru_cache(maxsize=None)
def _chunk(cfg, paged=False):
    return jax.jit(lambda p, t, lo, n, cache, table, slot:
                   mimo_v2.forward_chunk(cfg, p, t, lo, n, cache, table,
                                         slot, paged_kernel=paged,
                                         interpret=paged))


ROUTES = pytest.mark.parametrize("paged", [False, True],
                                 ids=["xla", "paged-interpret"])


@pytest.fixture
def small_blocks(monkeypatch):
    """The fused prefill kernel's blocks cut to this model's size, so a
    prompt of 40 tokens walks several key blocks a query block, some
    plain, some masked, some skipped; the programs traced under them
    are dropped with them."""
    monkeypatch.setattr(mimo_v2, "FUSED_BLOCKS", (8, 16))
    _packed.cache_clear(), _chunk.cache_clear()
    yield
    _packed.cache_clear(), _chunk.cache_clear()


class Cache:
    """`slots` sequences' caches in the leaves the engine model names,
    driven through the model's three functions as the engine's programs
    drive them."""

    def __init__(self, cfg, params, slots=3, blocks=30, paged=False):
        self.cfg, self.params, self.slots = cfg, params, slots
        em = engine_model_for(cfg, kv_dtype="model", block_size=BS, chunk=1,
                              paged=paged, interpret=True)
        self.cache = tuple(
            jnp.zeros((leaf.layers,) + ((slots,) if leaf.per_slot
                                        else (blocks + 1, BS)) + leaf.tail,
                      leaf.dtype) for leaf in em.cache_leaves)
        per = blocks // slots
        self.tables = [list(range(1 + s * per, 1 + (s + 1) * per))
                       for s in range(slots)]
        self.paged = paged
        self._decode = jax.jit(lambda p, c, t, q, tb, live: mimo_v2.decode_step(
            cfg, p, t, c, q, tb, live=live, paged_kernel=paged,
            interpret=True))

    def pack(self, prompts, slots, N):
        """Whole prompts end to end, as the engine's `_pack_arrays`
        lays them out and `packed_prefill_program` writes them."""
        K = 4
        tok, posn = np.zeros(N, np.int32), np.zeros(N, np.int32)
        seg, blk = np.full(N, -1, np.int32), np.zeros(N // BS, np.int32)
        last, sl, at = np.zeros(K, np.int32), np.full(K, self.slots), 0
        for i, (p, s) in enumerate(zip(prompts, slots)):
            T, nb = len(p), -(-len(p) // BS)
            tok[at:at + T], seg[at:at + T] = p, i
            posn[at:at + T] = np.arange(T)
            blk[at // BS:at // BS + nb] = self.tables[s][:nb]
            last[i], sl[i] = at + T - 1, s
            at += nb * BS
        packed = Packed(jnp.asarray(last), jnp.asarray(seg), jnp.asarray(posn))
        logits, (ks, vs), ring = _packed(self.cfg, self.paged)(
            self.params, jnp.asarray(tok)[None], self.cache[2:], packed,
            jnp.asarray(sl, jnp.int32))
        k_pool, v_pool = (
            pool.at[:, blk].set(rows[:, 0].reshape(
                (rows.shape[0], N // BS, BS, -1)))
            for pool, rows in zip(self.cache[:2], (ks, vs)))
        self.cache = (k_pool, v_pool, *ring)
        return np.asarray(logits[0])

    def chunk(self, toks, lo, hi, slot, N=None):
        N = N or -(-(hi - lo) // BS) * BS
        buf = np.zeros(N, np.int32)
        buf[:hi - lo] = toks[lo:hi]
        logits, self.cache = _chunk(self.cfg, self.paged)(
            self.params, jnp.asarray(buf), jnp.int32(lo), jnp.int32(hi - lo),
            self.cache, jnp.asarray(self.tables[slot], jnp.int32),
            jnp.int32(slot))
        return np.asarray(logits)

    def decode(self, toks, pos, live=None):
        """One step of every slot: `toks` / `pos` [slots]."""
        logits, self.cache, stats = self._decode(
            self.params, self.cache, jnp.asarray(toks, jnp.int32),
            jnp.asarray(pos, jnp.int32),
            jnp.asarray(self.tables, jnp.int32), live)
        return np.asarray(logits), stats


# ----------------------------------------------------------------------
def test_the_published_pattern_and_the_cache_spec():
    full = mimo_v2.MimoV2Config()
    assert (full.n_layers, full.n_full_layers, full.n_swa_layers) == (48, 9, 39)
    assert full.layer_pattern[:7] == (0, 1, 1, 1, 1, 0, 1)
    assert full.n_moe_layers == 47 and full.ring_rows == 128
    cut = dataclasses.replace(
        full, layer_pattern=full.layer_pattern[:7],
        moe_layers=full.moe_layers[:7], experts_held=16, vocab_size=19072)
    model = engine_model_for(cut, kv_dtype="model", block_size=16, chunk=8,
                             paged=False, interpret=False)
    # paged leaves first, of DIFFERENT tails, each counting its own
    # layers; a token's heads side by side in one row of whole lanes
    assert [(l.name, l.per_slot, l.layers, l.tail)
            for l in model.cache_leaves] == [
        ("k", False, 2, (768,)), ("v", False, 2, (512,)),
        ("swa_k", True, 5, (128, 1536)), ("swa_v", True, 5, (128, 1024))]
    pool = BlockPool(40961, spec=model.cache_leaves, slots=128)
    assert pool.leaf_shapes(7, 16) == [
        ((2, 40961, 16, 768), jnp.bfloat16),
        ((2, 40961, 16, 512), jnp.bfloat16),
        ((5, 128, 128, 1536), jnp.bfloat16),
        ((5, 128, 128, 1024), jnp.bfloat16)]
    # the full layers alone a token; the rings a slot, whatever its length
    assert pool.bytes_per_token(7) == 2 * 2560
    assert pool.bytes_per_slot(7) == 5 * 128 * 5120
    assert model.state_carries_chunks and model.segmented
    with pytest.raises(ValueError, match="int8"):
        engine_model_for(cut, kv_dtype="int8", block_size=16, chunk=8,
                         paged=False, interpret=False)


def test_the_tiny_contexts_wrap_the_ring():
    assert LONG >= 3 * CFG.window + 2 and CFG.ring_rows == CFG.window
    assert CFG.layer_pattern.count(mimo_v2.SWA) == 3
    assert CFG.n_kv_heads != CFG.swa_n_kv_heads
    assert CFG.head_dim != CFG.v_head_dim and CFG.rotary_dim < CFG.head_dim


@pytest.mark.parametrize("n", [LONG, 13])
def test_forward_equals_the_reference(params, n):
    toks = tokens(n)
    want = ref_logits(CFG, params, toks)
    assert np.abs(forward_logits(CFG, params, toks) - want).max() < TOL
    assert np.abs(want).mean() > 0.3   # logits of order one


def test_forward_with_the_held_pairs_compacted_equals_the_reference(
        params, monkeypatch):
    """A program long enough for `parallel/moe.COMPACT_FROM` moves the
    held pairs alone through its expert layers: the reference's logits
    all the same (the constant lowered to this model's size; a fresh
    trace, the module's jitted forward was traced above it)."""
    monkeypatch.setattr(moe, "COMPACT_FROM", 64)
    slabs, traced = moe._held_slabs, []
    monkeypatch.setattr(moe, "_held_slabs", lambda *a, **kw: (
        traced.append(kw["rows"]), slabs(*a, **kw))[1])
    toks = tokens(LONG)
    assert moe.slab_rows(LONG * CFG.top_k, CFG.experts_held,
                         CFG.n_routed_experts)
    got = jax.jit(lambda p, t: mimo_v2.forward(CFG, p, t)[0][0])(
        params, jnp.asarray([toks], jnp.int32))
    assert np.abs(np.asarray(got) - ref_logits(CFG, params, toks)).max() < TOL
    assert len(traced) == sum(CFG.moe_layers)


def test_bf16_breaks_it(params):
    """The tolerance is one a bfloat16-for-float32 swap breaks."""
    toks = tokens(LONG)
    want = ref_logits(CFG, params, toks)
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    low = jax.tree.map(lambda v: v.astype(jnp.bfloat16)
                       if v.dtype == jnp.float32 and v.ndim > 1 else v, params)
    assert np.abs(forward_logits(cfg, low, toks) - want).max() > 20 * TOL


@ROUTES
def test_prefill_then_decode_through_both_caches_equals_the_reference(
        params, paged, small_blocks):
    """A packed prefill as admission runs it (two prompts into slots 2
    and 0), then `decode_step` on the engine's own leaves, teacher-
    forced PAST THE RING'S WRAP: every logit against the reference's
    full forward pass, which has no cache of either kind.  Slot 1 is
    dead throughout and writes nothing."""
    seqs = [tokens(LONG, 1), tokens(LONG - 7, 2)]
    want = [ref_logits(CFG, params, s) for s in seqs]
    starts = [12, 5]
    c = Cache(CFG, params, paged=paged)
    first = c.pack([s[:n] for s, n in zip(seqs, starts)], [2, 0], 32)
    for i, n in enumerate(starts):
        assert np.abs(first[i] - want[i][n - 1]).max() < TOL
    slot_of, live = {0: 2, 1: 0}, jnp.asarray([True, False, True])
    before = [np.asarray(x[:, 1]) for x in c.cache[2:]]
    for step in range(LONG - 12):
        toks, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
        for i, s in slot_of.items():
            p = min(starts[i] + step, len(seqs[i]) - 1)
            toks[s], pos[s] = seqs[i][p], p
        logits, _ = c.decode(toks, pos, live)
        for i, s in slot_of.items():
            p = starts[i] + step
            if p < len(seqs[i]):
                assert np.abs(logits[s] - want[i][p]).max() < TOL, (i, p)
    for x, b in zip(c.cache[2:], before):   # the dead slot's ring
        assert np.array_equal(np.asarray(x[:, 1]), b)


def test_the_ring_reads_what_every_row_kept_reads(params):
    """A window layer on its ring against the same layers with every
    row kept (`forward` holds all rows of the sequence): the same
    logits at every position past the wrap, to rounding."""
    seq = tokens(LONG, 3)
    kept = forward_logits(CFG, params, seq)
    c = Cache(CFG, params, slots=1, blocks=8)
    c.pack([seq[:4]], [0], 8)
    for p in range(4, LONG):
        logits, _ = c.decode([seq[p]], [p])
        assert np.abs(logits[0] - kept[p]).max() < 1e-4, p
    # the ring holds exactly the last `window` positions' rows
    held = np.asarray(mimo_v2._ring_index(CFG, jnp.asarray(LONG)))
    assert sorted(held) == list(range(LONG - CFG.window, LONG))
    assert all(h % CFG.ring_rows == r for r, h in enumerate(held))


@ROUTES
@pytest.mark.parametrize("chunks", [
    [(0, 16), (16, 32), (32, LONG)], [(0, 24), (24, LONG)],
    [(0, 8), (8, 16), (16, 24), (24, 32), (32, LONG)]])
def test_a_prompt_admitted_in_chunks_equals_one_program(params, chunks, paged,
                                                        small_blocks):
    """A prompt chunk by chunk, each behind the request's own blocks and
    the slot's ring, against the same prompt in one program: the last
    token's logits, the full layers' blocks, the ring at the prompt's
    end, and the decoding that follows.  `paged`: the full layers fold
    in the fused kernel (interpreted), the table's rows gathered once a
    layer, each chunk from its own `lo`."""
    seq = tokens(LONG + 6, 4)
    whole = Cache(CFG, params, paged=paged)
    first = whole.pack([seq[:LONG]], [1], -(-LONG // BS) * BS)[0]
    c = Cache(CFG, params, paged=paged)
    for lo, hi in chunks:
        logits = c.chunk(seq, lo, hi, slot=1, N=-(-LONG // BS) * BS)
    assert np.abs(logits - first).max() < 1e-4
    nb = LONG // BS
    for a, b in zip(c.cache[:2], whole.cache[:2]):
        blk = jnp.asarray(c.tables[1][:nb])
        assert np.abs(np.asarray(a[:, blk]) - np.asarray(b[:, blk])).max() < 1e-4
    for a, b in zip(c.cache[2:], whole.cache[2:]):
        assert np.abs(np.asarray(a[:, 1]) - np.asarray(b[:, 1])).max() < 1e-4
    want = ref_logits(CFG, params, seq)
    assert np.abs(logits - want[LONG - 1]).max() < TOL
    live = jnp.asarray([False, True, False])
    for p in range(LONG, LONG + 6):
        out, _ = c.decode([0, seq[p], 0], [0, p, 0], live)
        assert np.abs(out[1] - want[p]).max() < TOL, p


@ROUTES
def test_packed_prompts_under_the_segment_and_window_masks(params, paged,
                                                           small_blocks):
    """Three prompts end to end in one row, two of them longer than the
    window: each one's logits are those of the prompt alone, and each
    slot's ring is the one the prompt alone leaves.  `paged`: the
    segments reach the fused kernel, whose walk skips the key blocks of
    the prompts before a query block's own."""
    prompts = [tokens(19, 5), tokens(3, 6), tokens(LONG - 9, 7)]
    c = Cache(CFG, params, paged=paged)
    got = c.pack(prompts, [1, 2, 0], 72)
    for i, (p, slot) in enumerate(zip(prompts, [1, 2, 0])):
        assert np.abs(got[i] - ref_logits(CFG, params, p)[-1]).max() < TOL
        alone = Cache(CFG, params, paged=paged)
        alone.pack([p], [slot], -(-len(p) // BS) * BS)
        held = np.asarray(mimo_v2._ring_index(CFG, jnp.asarray(len(p)))) >= 0
        for a, b in zip(c.cache[2:], alone.cache[2:]):
            a, b = np.asarray(a[:, slot]), np.asarray(b[:, slot])
            assert np.abs(a[:, held] - b[:, held]).max() < 1e-5


def test_the_sink_takes_mass_and_weighs_no_value(params):
    """The sink is one more column of the softmax whose probability is
    dropped: a row with a large `s_h` loses nearly all its mass (the
    window layers then add next to nothing), `sink_off` (the column left
    out) is another result, and both are the reference's."""
    toks = tokens(20, 8)
    off = dataclasses.replace(CFG, swa_sink=False)
    base, no_sink = (forward_logits(c, params, toks) for c in (CFG, off))
    assert np.abs(base - no_sink).max() > 100 * TOL
    assert np.abs(no_sink - ref_logits(off, params, toks)).max() < TOL
    big = {**params, "layers": [
        {**l, "sink": jnp.full_like(l["sink"], 40.0)} if "sink" in l else l
        for l in params["layers"]]}
    drowned = forward_logits(CFG, big, toks)
    assert np.abs(drowned - ref_logits(CFG, big, toks)).max() < TOL
    # with all mass on the sink a window layer's attention adds ~0
    q = jnp.ones((1, 5, 2, 2, 24))
    k, v = jnp.ones((1, 7, 2, 24)), jnp.ones((1, 7, 2, 16))
    mask = jnp.ones((1, 5, 7), bool)
    plain = mimo_v2._attend(CFG, q, k, v, mask, None)
    sunk = mimo_v2._attend(CFG, q, k, v, mask, jnp.full((2, 2), 60.0))
    assert np.allclose(np.asarray(plain), 1.0, atol=1e-5)
    assert np.abs(np.asarray(sunk)).max() < 1e-6
    # a sink at the row's own maximum halves... one more equal column:
    # 7 equal scores and a sink of the same value keep 7/8 of the mass
    score = float(24 * 24 ** -0.5)
    even = mimo_v2._attend(CFG, q, k, v, mask, jnp.full((2, 2), score))
    assert np.allclose(np.asarray(even), 7 / 8, atol=1e-5)


def test_window_off_is_another_result(params):
    toks = tokens(LONG, 9)
    wide = dataclasses.replace(CFG, window=4 * CFG.window)
    assert np.abs(forward_logits(wide, params, toks)
                  - forward_logits(CFG, params, toks)).max() > 100 * TOL


def _moe_layer(E=16):
    k = jax.random.split(jax.random.PRNGKey(11), 5)
    n = lambda i, shape: jax.random.normal(k[i], shape) * 0.2  # noqa: E731
    return {"router": n(0, (32, E)), "router_bias": n(1, (E,)) * 0.1,
            "e_gate": n(2, (E, 32, 16)), "e_up": n(3, (E, 32, 16)),
            "e_down": n(4, (E, 16, 32))}


def _share(layer, lo, n):
    return {**layer, **{k: layer[k][lo:lo + n]
                        for k in ("e_gate", "e_up", "e_down")}}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The deployment's 16 shares of an expert layer (one sixteenth of
    the experts each, the router whole), each computed as this chip
    computes its own, add up to the reference's uncut layer; there is
    no shared expert to count once."""
    layer, E = _moe_layer(), 16
    h = jax.random.normal(jax.random.PRNGKey(5), (24, 32))
    kw = dict(top_k=4, scale=1.0, quant=ref._identity)
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed(h, layer, offset=0, **kw)
        total = jnp.zeros_like(uncut)
        for s in range(16):
            part, stats = moe.dropless_moe(
                h, _share(layer, s, E // 16), top_k=4, scale=1.0,
                route_eps=1e-20, dtype=jnp.float32, held=(s, E // 16))
            want = ref.routed(h, _share(layer, s, 1), offset=s, **kw)
            assert np.abs(np.asarray(part) - np.asarray(want)).max() < 1e-5
            assert int(stats["experts_touched"]) <= 1
            total = total + part
    assert np.abs(np.asarray(total) - np.asarray(uncut)).max() < 1e-5


# ----------------------------------------------------------------------
# through the engine
# ----------------------------------------------------------------------
def greedy(params, prompt, n, T=80):
    """A loop over `forward`, the sequence right-padded to one shape (a
    later row changes no earlier one's logits)."""
    toks = list(prompt)
    for _ in range(n):
        row = forward_logits(CFG, params, toks + [0] * (T - len(toks)))
        toks.append(int(np.argmax(row[len(toks) - 1])))
    return toks[len(prompt):]


@pytest.fixture(scope="module")
def engine(params):
    eng = LlamaEngine(CFG, params, slots=3, chunk=2, block_size=BS,
                      max_len=96, kv_blocks=30, prefill_chunk=16)
    yield eng
    eng.shutdown()


def test_engine_packed_and_chunked_admission_and_decode(engine, params):
    """Both cache kinds behind `LlamaEngine`: short prompts packed, long
    ones admitted in chunks of 16 that carry the slot's ring, all
    decoded past the ring's wrap; greedy tokens are a loop's over
    `forward`."""
    prompts = [tokens(n, 20 + n).tolist() for n in (5, 16, 40, 33, 12, 64)]
    futs = [engine.submit(p, 14) for p in prompts]
    for p, f in zip(prompts, futs):
        assert f.result(timeout=300) == greedy(params, p, 14)
    s = engine.stats()
    assert s["cache_bytes_per_token"] == 2 * 2 * (24 + 16) * 4
    assert s["cache_bytes_per_slot"] == 3 * 8 * 4 * (24 + 16) * 4
    by_len = {r["tokens_in"]: r for r in s["request_ring"]}
    assert [by_len[n]["prefill_chunks"] for n in (5, 16, 40, 33, 12, 64)] \
        == [0, 0, 3, 3, 0, 4]


def test_engine_admission_and_decode_on_the_kernel_route(params,
                                                         small_blocks):
    """The same through the route the chip takes, its kernels
    interpreted: the paged decode kernels, and the fused prefill fold
    in the packed and the chunked admission programs alike."""
    eng = LlamaEngine(CFG, params, slots=3, chunk=2, block_size=BS,
                      max_len=96, kv_blocks=30, prefill_chunk=16,
                      decode_kernel="pallas", kernel_interpret=True)
    try:
        prompts = [tokens(n, 20 + n).tolist() for n in (5, 40, 12)]
        futs = [eng.submit(p, 6) for p in prompts]
        for p, f in zip(prompts, futs):
            assert f.result(timeout=600) == greedy(params, p, 6)
        by_len = {r["tokens_in"]: r for r in eng.stats()["request_ring"]}
        assert [by_len[n]["prefill_chunks"] for n in (5, 40, 12)] == [0, 3, 0]
    finally:
        eng.shutdown()


def test_engine_tick_fields(engine):
    engine.submit(tokens(21, 30).tolist(), 6).result(timeout=300)
    ticks = [t for t in engine.stats()["tick_ring"]
             if t.get("full_cache_tokens_live")]
    assert ticks
    t = ticks[-1]
    assert t["window_rows_live"] <= CFG.window * t["state_rows_live"]
    assert t["ring_bytes_live"] == t["state_rows_live"] * \
        engine.stats()["cache_bytes_per_slot"]
    assert t["full_cache_tokens_live"] >= t["window_rows_live"]
    assert any(t.get("experts_held") == CFG.experts_held
               for t in engine.stats()["tick_ring"])


def test_admission_is_bounded_by_slots_and_by_full_layer_blocks(
        params, monkeypatch):
    monkeypatch.setenv("RT_ENGINE_TICK_RING", "4096")  # every tick
    """A request needs a slot AND the full layers' blocks: with blocks
    for two sequences and three slots the third waits for blocks; with
    blocks to spare and three slots the fourth waits for a slot."""
    for kv_blocks, at_once in ((12, 2), (40, 3)):
        eng = LlamaEngine(CFG, params, slots=3, chunk=2, block_size=BS,
                          max_len=48, kv_blocks=kv_blocks, prefill_chunk=16)
        try:
            futs = [eng.submit(tokens(20, 40 + i).tolist(), 24)
                    for i in range(4)]
            assert all(len(f.result(timeout=300)) == 24 for f in futs)
            # slots held, from the ticks' own record: what was admitted
            # less what gave its slot back (every request here hands
            # its slot over at the dispatch of its last chunk and is
            # `active` until that chunk's harvest, so `active` may read
            # one more).  A slot given back twice, or an admission past
            # the free slots or the blocks, reads over `at_once`
            st = eng.stats()
            assert st["handoffs_total"] == 4
            held = np.cumsum([t["admitted"] - t["handed_off"]
                              for t in st["tick_ring"]])
            assert held.max() == at_once and held.min() >= 0
            assert held[-1] == 0
        finally:
            eng.shutdown()


def test_what_the_cache_cannot_do_is_refused(params):
    kw = dict(slots=2, chunk=2, block_size=BS, max_len=48, kv_blocks=12)
    with pytest.raises(PrefixCacheUnsupportedError):
        LlamaEngine(CFG, params, prefix_cache=True, **kw)
    with pytest.raises(ValueError, match="int8"):
        LlamaEngine(CFG, params, kv_dtype="int8", **kw)


@pytest.mark.parametrize("model", [brumby, lfm2], ids=["brumby", "lfm2"])
def test_a_state_a_prefill_leaves_once_is_not_carried_across_chunks(model):
    """`prefill_chunk` asks the MODEL whether its per-slot leaf carries
    across chunks: a ring does, a retention or convolution state as
    wired today does not, and is refused as before."""
    cfg = (brumby.BrumbyConfig.tiny() if model is brumby
           else lfm2.Lfm2MoeConfig.tiny())
    p = model.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="per-slot state has not"):
        LlamaEngine(cfg, p, slots=2, chunk=2, block_size=8, max_len=48,
                    kv_blocks=12, prefill_chunk=16)
