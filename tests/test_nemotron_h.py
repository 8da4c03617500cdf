"""`models/nemotron_h.py` (Mamba-2 layers on a per-slot recurrent state,
an attention layer on paged folded pools, a share of a latent expert
layer) against the plain float32 reference
`benchmarks/reference/nemotron_h.py`, at tiny widths on the CPU: each
mixer and the whole forward, packed prefill + decoding through the three
leaves, chunked admission that RESUMES from the slot's state, packed
prompts' states, the expert layer's four shares, and the engine end to
end."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as ref
from ray_tpu.exceptions import PrefixCacheUnsupportedError
from ray_tpu.models import nemotron_h as nh
from ray_tpu.models.llama import Packed
from ray_tpu.parallel import moe
from ray_tpu.serve.engine_model import engine_model_for
from ray_tpu.serve.kv_cache import BlockPool
from ray_tpu.serve.llm_engine import LlamaEngine

CFG = nh.NemotronHConfig.tiny()
BS = 8
# float32 against float32 with sums in another order (a chunked scan
# against a token at a time, a running softmax against a dense one, a
# grouped product against experts one at a time), logits of order one; a
# bfloat16 model against the same reference reads 100 x this
# (test_bf16_breaks_it)
TOL = 3e-4
# several scan chunks (8) and cache blocks, no multiple of either
LONG = 43


@pytest.fixture(scope="module")
def params():
    return nh.init_params(CFG, jax.random.PRNGKey(7), std=0.2)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, size=n)


def published_keys(cfg):
    """The config as the published keys the reference reads."""
    return {"hybrid_override_pattern": cfg.pattern,
            "layer_norm_epsilon": cfg.norm_eps,
            "mamba_num_heads": cfg.mamba_heads,
            "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.n_groups,
            "ssm_state_size": cfg.state_size,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "num_experts_per_tok": cfg.top_k,
            "routed_scaling_factor": cfg.routed_scale}


def ref_logits(cfg, params, toks, upto=None):
    m = published_keys(cfg)
    with jax.default_matmul_precision("highest"):
        x = ref.embed(jnp.asarray(toks), params["tok_emb"])
        for l, w in enumerate(params["layers"][:upto]):
            x = ref.layer(x, w, qblock=len(toks),
                          **ref.layer_kwargs(m, l, offset=cfg.expert_offset))
        if upto is not None:
            return np.asarray(x)
        return np.asarray(ref.head(x, params["final_norm"],
                                   params["lm_head"], cfg.norm_eps))


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(lambda p, t: nh.forward(cfg, p, t)[0][0])


def forward_logits(cfg, params, toks):
    return np.asarray(_forward(cfg)(params, jnp.asarray([toks], jnp.int32)))


@functools.lru_cache(maxsize=None)
def _packed(cfg, paged=False, scan=False):
    """`paged`: the route the chip takes, its kernels interpreted (the
    fused prefill fold and the scan); `scan`: the scan's kernel alone."""
    return jax.jit(lambda p, t, state, packed, slots: nh.forward(
        cfg, p, t, state, packed=packed, slots=slots, paged_kernel=paged,
        interpret=paged or scan))


@functools.lru_cache(maxsize=None)
def _chunk(cfg, paged=False, scan=False):
    return jax.jit(lambda p, t, lo, n, cache, table, slot:
                   nh.forward_chunk(cfg, p, t, lo, n, cache, table, slot,
                                    paged_kernel=paged,
                                    interpret=paged or scan))


ROUTES = pytest.mark.parametrize("paged", [False, True],
                                 ids=["xla", "paged-interpret"])


@pytest.fixture
def small_blocks(monkeypatch):
    """The fused prefill kernel's blocks cut to this model's size; the
    programs traced under them are dropped with them."""
    monkeypatch.setattr(nh, "FUSED_BLOCKS", (8, 16))
    _packed.cache_clear(), _chunk.cache_clear()
    yield
    _packed.cache_clear(), _chunk.cache_clear()


class Cache:
    """`slots` sequences' caches in the leaves the engine model names,
    driven through the model's three functions as the engine's programs
    drive them."""

    def __init__(self, cfg, params, slots=3, blocks=30, paged=False,
                 scan=False):
        self.cfg, self.params, self.slots = cfg, params, slots
        self.scan = scan
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
        self._decode = jax.jit(lambda p, c, t, q, tb, live: nh.decode_step(
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
        logits, (ks, vs), state = _packed(self.cfg, self.paged, self.scan)(
            self.params, jnp.asarray(tok)[None], self.cache[2:], packed,
            jnp.asarray(sl, jnp.int32))
        k_pool, v_pool = (
            pool.at[:, blk].set(rows[:, 0].reshape(
                (rows.shape[0], N // BS, BS, -1)))
            for pool, rows in zip(self.cache[:2], (ks, vs)))
        self.cache = (k_pool, v_pool, *state)
        return np.asarray(logits[0])

    def chunk(self, toks, lo, hi, slot, N=None):
        N = N or -(-(hi - lo) // BS) * BS
        buf = np.zeros(N, np.int32)
        buf[:hi - lo] = toks[lo:hi]
        logits, self.cache = _chunk(self.cfg, self.paged, self.scan)(
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
    """The benchmark's cut at the published widths: three leaves over
    disjoint layers, and what a token and a slot cost."""
    cut = nh.NemotronHConfig(vocab_size=32768, experts_held=128)
    assert (cut.n_layers, cut.n_mamba_layers, cut.n_attn_layers,
            cut.n_moe_layers) == (11, 5, 1, 5)
    assert (cut.d_inner, cut.conv_dim) == (8192, 10240)
    shp = nh.layer_shapes(cut, 0)
    assert shp["in_proj"] == (4096, 18560)
    count = lambda i: sum(int(np.prod(s))  # noqa: E731
                          for s in nh.layer_shapes(cut, i).values())
    # 109.64M a Mamba layer, 35.66M the attention layer, 54.53M an expert
    # layer outside its experts and 5.505M an expert
    assert round(count(0) / 1e6, 2) == 109.64
    assert round(count(7) / 1e6, 2) == 35.66
    assert round((count(1) - 128 * 2 * 1024 * 2688) / 1e6, 2) == 54.53
    model = engine_model_for(cut, kv_dtype="model", block_size=16, chunk=8,
                             paged=True, interpret=False)
    assert [(l.name, l.per_slot, l.layers, l.tail)
            for l in model.cache_leaves] == [
        ("k", False, 1, (256,)), ("v", False, 1, (256,)),
        ("ssm", True, 5, (128, 64, 128)), ("conv", True, 5, (30720,))]
    pool = BlockPool(40961, spec=model.cache_leaves, slots=128)
    assert pool.leaf_shapes(11, 16) == [
        ((1, 40961, 16, 256), jnp.bfloat16),
        ((1, 40961, 16, 256), jnp.bfloat16),
        ((5, 128, 128, 64, 128), jnp.float32),
        ((5, 128, 30720), jnp.bfloat16)]
    # one attention layer a token; the states a slot, whatever its length
    assert pool.bytes_per_token(11) == 1024
    assert pool.bytes_per_slot(11) == 5 * (128 * 64 * 128 * 4 + 30720 * 2)
    assert model.state_carries_chunks and model.segmented
    assert model.aux_rows == 3
    with pytest.raises(ValueError, match="int8"):
        engine_model_for(cut, kv_dtype="int8", block_size=16, chunk=8,
                         paged=False, interpret=False)


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_each_mixer_equals_the_reference(params, kind):
    """The stack cut after its first layer of each kind: the hidden
    state behind that mixer against the reference's."""
    upto = CFG.pattern.index(kind) + 1
    cfg = dataclasses.replace(CFG, pattern=CFG.pattern[:upto])
    toks = tokens(LONG)
    cut = {**params, "layers": params["layers"][:upto]}
    want = ref_logits(cfg, cut, toks, upto=upto)
    # the model's own hidden state: the head left out by an identity
    eye = {**cut, "final_norm": jnp.ones_like(params["final_norm"]),
           "lm_head": jnp.eye(CFG.dim)}
    got = forward_logits(dataclasses.replace(cfg, vocab_size=CFG.dim), eye,
                         toks)
    normed = want / np.sqrt((want ** 2).mean(-1, keepdims=True) + cfg.norm_eps)
    assert np.abs(got - normed).max() < TOL


@pytest.mark.parametrize("n", [LONG, 5])
def test_forward_equals_the_reference(params, n):
    toks = tokens(n)
    want = ref_logits(CFG, params, toks)
    assert np.abs(forward_logits(CFG, params, toks) - want).max() < TOL
    assert np.abs(want).mean() > 0.3   # logits of order one


def test_bf16_breaks_it(params):
    """The tolerance is one a bfloat16-for-float32 swap breaks."""
    toks = tokens(LONG)
    want = ref_logits(CFG, params, toks)
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    low = jax.tree.map(lambda v: v.astype(jnp.bfloat16)
                       if v.dtype == jnp.float32 and v.ndim > 1 else v, params)
    assert np.abs(forward_logits(cfg, low, toks) - want).max() > 20 * TOL


def test_the_state_is_read(params):
    """Tokens far back move a late logit THROUGH the state alone where
    attention is taken out: the recurrence is no window."""
    cfg = dataclasses.replace(CFG, pattern="MEMEM")
    p = {**params, "layers": [l for l, k in zip(params["layers"], CFG.pattern)
                              if k != "*"][:5]}
    a, b = tokens(LONG, 1), tokens(LONG, 1)
    b[0] = (b[0] + 1) % CFG.vocab_size or 1
    assert np.abs(forward_logits(cfg, p, a)[20] - forward_logits(cfg, p, b)[20]
                  ).max() > 10 * TOL


@ROUTES
def test_prefill_then_decode_through_the_three_leaves_equals_the_reference(
        params, paged, small_blocks):
    """A packed prefill as admission runs it (two prompts into slots 2
    and 0), then `decode_step` on the engine's own leaves, teacher-
    forced: every logit against the reference's full forward pass, which
    has neither cache nor state.  Slot 1 is dead throughout and writes
    nothing."""
    seqs = [tokens(LONG, 1), tokens(LONG - 7, 2)]
    want = [ref_logits(CFG, params, s) for s in seqs]
    starts = [12, 5]
    c = Cache(CFG, params, paged=paged)
    first = c.pack([s[:n] for s, n in zip(seqs, starts)], [2, 0], 32)
    for i, n in enumerate(starts):
        assert np.abs(first[i] - want[i][n - 1]).max() < TOL
    slot_of, live = {0: 2, 1: 0}, jnp.asarray([True, False, True])
    c.cache = (*c.cache[:2], *(x.at[:, 1].set(1) for x in c.cache[2:]))
    before = [np.asarray(x[:, 1]) for x in c.cache[2:]]
    for step in range(LONG - 12):
        toks, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
        for i, s in slot_of.items():
            p = min(starts[i] + step, len(seqs[i]) - 1)
            toks[s], pos[s] = seqs[i][p], p
        logits, stats = c.decode(toks, pos, live)
        for i, s in slot_of.items():
            p = starts[i] + step
            if p < len(seqs[i]):
                assert np.abs(logits[s] - want[i][p]).max() < TOL, (i, p)
    for x, b in zip(c.cache[2:], before):   # the dead slot's states
        assert np.array_equal(np.asarray(x[:, 1]), b)
    # two live rows x top-6 over 3 expert layers, a quarter held on average
    assert 0 < int(stats["held_pairs"]) <= 2 * CFG.top_k * CFG.n_moe_layers


@ROUTES
@pytest.mark.parametrize("chunks", [
    [(0, 16), (16, 32), (32, LONG)], [(0, 24), (24, LONG)],
    [(0, 8), (8, 16), (16, 24), (24, 32), (32, LONG)]])
def test_a_prompt_admitted_in_chunks_equals_one_program(params, chunks, paged,
                                                        small_blocks):
    """A prompt chunk by chunk, each RESUMING from the slot's `ssm` and
    `conv` and behind the request's own blocks, against the same prompt
    in one program: the last token's logits, the attention layer's
    blocks, BOTH states at the prompt's end, and the decoding that
    follows.  The slot held another sequence's state before: a prompt's
    first chunk starts from zero whatever is there."""
    seq = tokens(LONG + 6, 4)
    whole = Cache(CFG, params, paged=paged)
    first = whole.pack([seq[:LONG]], [1], -(-LONG // BS) * BS)[0]
    c = Cache(CFG, params, paged=paged)
    c.cache = (*c.cache[:2], *(x + 3 for x in c.cache[2:]))
    for lo, hi in chunks:
        logits = c.chunk(seq, lo, hi, slot=1, N=-(-LONG // BS) * BS)
    assert np.abs(logits - first).max() < 1e-4
    nb = LONG // BS
    for a, b in zip(c.cache[:2], whole.cache[:2]):
        blk = jnp.asarray(c.tables[1][:nb])
        assert np.abs(np.asarray(a[:, blk]) - np.asarray(b[:, blk])).max() < 1e-4
    for a, b in zip(c.cache[2:], whole.cache[2:]):
        assert np.abs(np.asarray(a[:, 1]) - np.asarray(b[:, 1])).max() < 1e-4
        assert np.abs(np.asarray(b[:, 1])).max() > 0.1
        # the other slots' states are nobody's to touch
        assert np.array_equal(np.asarray(a[:, 0]), np.full_like(a[:, 0], 3))
    want = ref_logits(CFG, params, seq)
    assert np.abs(logits - want[LONG - 1]).max() < TOL
    live = jnp.asarray([False, True, False])
    for p in range(LONG, LONG + 6):
        out, _ = c.decode([0, seq[p], 0], [0, p, 0], live)
        assert np.abs(out[1] - want[p]).max() < TOL, p


@pytest.mark.parametrize("case", ["whole", "two-chunks", "packed"])
def test_the_scan_kernel_interpreted_equals_the_xla_route(params, case):
    """`forward` and `forward_chunk` with the scan as its Pallas kernel
    (interpreted; everything else plain XLA) against the XLA route: a
    prompt in one piece, in two chunks of which the second RESUMES
    inside a scan chunk's reach, and packed beside another that starts
    mid-chunk; the logits and BOTH end states of every Mamba layer."""
    seq, other = tokens(LONG, 4), tokens(13, 8)
    routes = [Cache(CFG, params), Cache(CFG, params, scan=True)]
    out = []
    for c in routes:
        if case == "whole":
            out.append(c.pack([seq], [1], -(-LONG // BS) * BS))
        elif case == "two-chunks":
            c.chunk(seq, 0, 24, slot=1, N=24)
            out.append(c.chunk(seq, 24, LONG, slot=1, N=24))
        else:
            out.append(c.pack([other, seq], [2, 1], 72))
    assert np.abs(out[0] - out[1]).max() < 1e-5
    assert np.abs(out[0]).max() > 0.1
    for slot in (1, 2) if case == "packed" else (1,):
        for a, b in zip(routes[0].cache[2:], routes[1].cache[2:]):
            a, b = np.asarray(a[:, slot]), np.asarray(b[:, slot])
            assert np.abs(a - b).max() < 1e-5 and np.abs(a).max() > 0.1
    # the kernel is in the traced program, once a Mamba layer
    toks = jnp.zeros((1, 16), jnp.int32)
    for interpret, calls in ((False, 0), (True, CFG.n_mamba_layers)):
        text = str(jax.make_jaxpr(lambda p, t: nh.forward(
            CFG, p, t, interpret=interpret))(params, toks))
        assert text.count("pallas_call") == calls


def test_a_chunk_that_starts_from_zero_is_another_result(params):
    """What `--control chunk_state_zero` does: the second chunk from a
    zeroed state differs at 100 x the tolerance."""
    seq = tokens(LONG, 4)
    c, z = Cache(CFG, params), Cache(CFG, params)
    for cache in (c, z):
        cache.chunk(seq, 0, 24, slot=1, N=24)
    z.cache = (*z.cache[:2], *(jnp.zeros_like(x) for x in z.cache[2:]))
    assert np.abs(c.chunk(seq, 24, LONG, slot=1, N=24)
                  - z.chunk(seq, 24, LONG, slot=1, N=24)).max() > 100 * TOL


@ROUTES
def test_packed_prompts_leave_the_states_they_leave_alone(params, paged,
                                                          small_blocks):
    """Three prompts end to end in one row, starting on block boundaries
    that are inside and at the edge of a scan chunk: each one's logits
    are those of the prompt alone, and each slot's `ssm` and `conv` are
    those the prompt alone leaves."""
    prompts = [tokens(19, 5), tokens(2, 6), tokens(LONG - 9, 7)]
    c = Cache(CFG, params, paged=paged)
    got = c.pack(prompts, [1, 2, 0], 72)
    for i, (p, slot) in enumerate(zip(prompts, [1, 2, 0])):
        assert np.abs(got[i] - ref_logits(CFG, params, p)[-1]).max() < TOL
        alone = Cache(CFG, params, paged=paged)
        alone.pack([p], [slot], -(-len(p) // BS) * BS)
        for a, b in zip(c.cache[2:], alone.cache[2:]):
            a, b = np.asarray(a[:, slot]), np.asarray(b[:, slot])
            assert np.abs(a - b).max() < 1e-5 and np.abs(b).max() > 0
    # a prompt shorter than the convolution's reach: zeros before it
    conv = np.asarray(c.cache[3][:, 2]).reshape(CFG.n_mamba_layers, 3, -1)
    assert not conv[:, 0].any() and conv[:, 1].any()


def _moe_layer(E=16, D=32, Z=16, I=24, Is=40):
    k = jax.random.split(jax.random.PRNGKey(11), 8)
    n = lambda i, shape: jax.random.normal(k[i], shape) * 0.2  # noqa: E731
    return {"router": n(0, (D, E)), "router_bias": n(1, (E,)) * 0.1,
            "w_in": n(2, (D, Z)), "e_up": n(3, (E, Z, I)),
            "e_down": n(4, (E, I, Z)), "w_out": n(5, (Z, D)),
            "s_up": n(6, (D, Is)), "s_down": n(7, (Is, D))}


def _share(layer, lo, n):
    return {**layer, **{k: layer[k][lo:lo + n] for k in ("e_up", "e_down")}}


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The deployment's 4 shares of an expert layer (a quarter of the
    experts each, the router whole), each computed as this chip computes
    its own and each through `W_out`, add up to the reference's uncut
    routed part; with the shared expert counted ONCE that is the
    reference's uncut layer."""
    layer, E = _moe_layer(), 16
    cfg = dataclasses.replace(CFG, dim=32, latent=16, n_routed_experts=E,
                              experts_held=E // 4, top_k=6)
    h = jax.random.normal(jax.random.PRNGKey(5), (24, 32))
    kw = dict(top_k=6, scale=cfg.routed_scale, quant=ref._identity)
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed(h, layer, offset=0, **kw) + ref.shared(h, layer)
        once = ref.shared(h, layer)
        total = once
        for s in range(4):
            part, stats = nh._latent_moe(
                dataclasses.replace(cfg, expert_offset=s * E // 4),
                _share(layer, s * E // 4, E // 4), h, kernel=False,
                interpret=False, row_mask=None)
            want = ref.routed(h, _share(layer, s * E // 4, E // 4),
                              offset=s * E // 4, **kw)
            assert np.abs(np.asarray(part - once) - np.asarray(want)
                          ).max() < 1e-4
            assert int(stats["held_pairs"]) <= 24 * 6
            total = total + (part - once)
    assert np.abs(np.asarray(total) - np.asarray(uncut)).max() < 1e-4


def test_a_two_matrix_expert_through_the_compact_form(params, monkeypatch):
    """A program long enough for `parallel/moe.COMPACT_FROM` moves the
    held pairs alone, two grouped products a slab: the reference's
    logits all the same."""
    monkeypatch.setattr(moe, "COMPACT_FROM", 64)
    slabs, traced = moe._held_slabs, []
    monkeypatch.setattr(moe, "_held_slabs", lambda *a, **kw: (
        traced.append(kw["rows"]), slabs(*a, **kw))[1])
    toks = tokens(LONG)
    got = jax.jit(lambda p, t: nh.forward(CFG, p, t)[0][0])(
        params, jnp.asarray([toks], jnp.int32))
    assert np.abs(np.asarray(got) - ref_logits(CFG, params, toks)).max() < TOL
    assert len(traced) == CFG.n_moe_layers


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
    """The three leaves behind `LlamaEngine`: short prompts packed, long
    ones admitted in chunks of 16 that resume from the slot's state, all
    decoded; greedy tokens are a loop's over `forward`, whose scan has
    no state to start from."""
    prompts = [tokens(n, 20 + n).tolist() for n in (5, 16, 40, 33, 12, 64)]
    futs = [engine.submit(p, 14) for p in prompts]
    for p, f in zip(prompts, futs):
        assert f.result(timeout=300) == greedy(params, p, 14)
    s = engine.stats()
    assert s["cache_bytes_per_token"] == 2 * 2 * 16 * 4
    assert s["cache_bytes_per_slot"] == 3 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    by_len = {r["tokens_in"]: r for r in s["request_ring"]}
    assert [by_len[n]["prefill_chunks"] for n in (5, 16, 40, 33, 12, 64)] \
        == [0, 0, 3, 3, 0, 4]
    # every chunk but a prompt's first resumed from the slot's state
    assert s["state_chunks_resumed"] == 2 + 2 + 3
    assert sum(t.get("state_chunks_resumed", 0) for t in s["tick_ring"]) == 7


def test_engine_admission_and_decode_on_the_kernel_route(params,
                                                         small_blocks):
    """The same through the route the chip takes, its kernels
    interpreted: the paged decode kernels on the folded pools and the
    fused prefill fold in both admission programs."""
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
    assert t["ssm_bytes_live"] == t["state_rows_live"] * \
        engine.stats()["cache_bytes_per_slot"]
    held = [t for t in engine.stats()["tick_ring"]
            if t.get("experts_held") == CFG.experts_held]
    assert held and all("held_pairs" in t for t in held)
    # a row-step sends at most top_k pairs a layer to held experts
    assert all(t["held_pairs"] <= 3 * 2 * CFG.top_k * CFG.n_moe_layers
               for t in held)
    assert any(t["held_pairs"] > 0 for t in held)


def test_admission_is_bounded_by_slots_and_by_blocks(params):
    """A request needs a slot AND the attention layer's blocks: with
    blocks for two sequences and three slots the third waits for blocks;
    with blocks to spare and three slots the fourth waits for a slot."""
    for kv_blocks, at_once in ((12, 2), (40, 3)):
        eng = LlamaEngine(CFG, params, slots=3, chunk=2, block_size=BS,
                          max_len=48, kv_blocks=kv_blocks, prefill_chunk=16)
        try:
            futs = [eng.submit(tokens(20, 40 + i).tolist(), 24)
                    for i in range(4)]
            most = 0
            while not all(f.done() for f in futs):
                # slots held: a request handed off at its last dispatch
                # is active until its harvest, its slot is not
                most = max(most, 3 - eng.stats()["free_slots"])
            assert most == at_once
            assert all(len(f.result()) == 24 for f in futs)
        finally:
            eng.shutdown()


def test_what_the_cache_cannot_do_is_refused(params):
    kw = dict(slots=2, chunk=2, block_size=BS, max_len=48, kv_blocks=12)
    with pytest.raises(PrefixCacheUnsupportedError):
        LlamaEngine(CFG, params, prefix_cache=True, **kw)
    with pytest.raises(ValueError, match="int8"):
        LlamaEngine(CFG, params, kv_dtype="int8", **kw)
