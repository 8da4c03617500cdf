"""`models/sdar.py` (the Qwen3-MoE block under block diffusion) against
the plain reference `benchmarks/reference/sdar.py`, on seeded random
weights at tiny sizes in float32; the softmax route beside the sigmoid
one; the denoising choice against the reference's.

TOLERANCE.  Both sides are float32 (the reference at matmul precision
`highest`, the model at the CPU's float32), so they differ by summation
order alone: logits of order 1 agree to ~1e-5; `TOL` = 2e-4 leaves room
for the layers and the grouped products' order.  A router, a softmax
or a confidence in bfloat16 (8 bits of mantissa: 4e-3 relative) moves a
logit by 1e-2 and more, fifty times `TOL`:
`test_a_bfloat16_router_fails_the_tolerance` holds that.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar as ref
from ray_tpu.models import llama, sdar
from ray_tpu.parallel import moe

TOL = 2e-4
STD = 0.2


def parts(cfg, params):
    """The reference's view of the model's tree: (ends, layers, kw)."""
    ends = {k: params[k] for k in ("tok_emb", "final_norm", "lm_head")}
    layers = [{k: v[l] for k, v in params["layers"].items()}
              for l in range(cfg.n_layers)]
    kw = dict(heads=cfg.n_heads, kv=cfg.n_kv_heads, hd=cfg.head_dim,
              theta=cfg.rope_theta, eps=cfg.norm_eps, top_k=cfg.top_k,
              renorm=cfg.norm_topk_prob)
    return ends, layers, kw


def model(B=4, seed=1, **over):
    # two layers: the second sees what the first's mask and route made
    cfg = dataclasses.replace(sdar.SdarMoeConfig.tiny(block_length=B),
                              **{"n_layers": 2, **over})
    return cfg, sdar.init_params(cfg, jax.random.PRNGKey(seed), std=STD)


def reference_logits(cfg, params, pad=16):
    """`logits_of` for `ref.generate`: one jitted program a padded
    length (padding behind the sequence changes no earlier position)."""
    ends, layers, kw = parts(cfg, params)
    fwd = jax.jit(lambda t: ref.forward(t, cfg.block_length, ends, layers, kw))

    def logits_of(tokens):
        T = tokens.shape[0]
        return fwd(jnp.pad(tokens, (0, -T % pad)))[:T]

    return logits_of


def _toks(n, seed=0, vocab=200):
    return np.random.default_rng(seed).integers(1, vocab, size=n)


# ------------------------------------------------------------- the route
def test_softmax_route_is_a_plain_topk_of_a_softmax():
    rng = np.random.default_rng(3)
    h = rng.normal(size=(33, 24)).astype(np.float32)
    wr = rng.normal(size=(24, 16)).astype(np.float32)
    p = jax.nn.softmax(jnp.asarray(h.astype(np.float64) @ wr), axis=-1)
    order = np.argsort(-np.asarray(p), axis=-1, kind="stable")[:, :4]
    top = np.take_along_axis(np.asarray(p), order, axis=-1)
    w, idx = moe.softmax_topk_route(jnp.asarray(h), jnp.asarray(wr), 4)
    assert np.array_equal(np.asarray(idx), order)
    np.testing.assert_allclose(np.asarray(w), top / top.sum(-1, keepdims=True),
                               rtol=2e-6)
    raw, _ = moe.softmax_topk_route(jnp.asarray(h), jnp.asarray(wr), 4,
                                    renorm=False)
    np.testing.assert_allclose(np.asarray(raw), top, rtol=2e-6)
    assert np.all(np.asarray(w).sum(-1) == pytest.approx(1.0, abs=1e-6))


def test_the_default_route_is_bit_for_bit_what_it_was():
    """`dropless_moe` without `route` is the sigmoid route every caller
    had: the same jaxpr as the call written out, the same bits."""
    rng = np.random.default_rng(5)
    D, E, I, N = 16, 8, 12, 21
    layer = {"router": jnp.asarray(rng.normal(size=(D, E)), jnp.float32),
             "router_bias": jnp.asarray(rng.normal(size=(E,)), jnp.float32),
             "e_gate": jnp.asarray(rng.normal(size=(E, D, I)), jnp.float32),
             "e_up": jnp.asarray(rng.normal(size=(E, D, I)), jnp.float32),
             "e_down": jnp.asarray(rng.normal(size=(E, I, D)), jnp.float32)}
    h = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    kw = dict(top_k=3, scale=2.5, route_eps=1e-6, dtype=jnp.float32)

    def written_out(h, layer, top_k, scale, eps):
        return moe.sigmoid_topk_route(h, layer["router"],
                                      layer["router_bias"], top_k, scale, eps)

    default = lambda h: moe.dropless_moe(h, layer, **kw)[0]       # noqa: E731
    named = lambda h: moe.dropless_moe(h, layer, route="sigmoid", **kw)[0]  # noqa: E731
    called = lambda h: moe.dropless_moe(h, layer, route=written_out, **kw)[0]  # noqa: E731
    assert str(jax.make_jaxpr(default)(h)) == str(jax.make_jaxpr(called)(h)) \
        == str(jax.make_jaxpr(named)(h))
    assert np.array_equal(np.asarray(default(h)), np.asarray(called(h)))
    soft = moe.dropless_moe(h, layer, route="softmax", **kw)[0]
    assert not np.allclose(np.asarray(soft), np.asarray(default(h)))


# ------------------------------------------------ the layer and its mask
@pytest.mark.parametrize("B", [4, 8])
def test_forward_equals_the_reference_under_the_block_causal_mask(B):
    cfg, params = model(B)
    toks = _toks(40, seed=B)
    want = np.asarray(reference_logits(cfg, params, pad=8)(jnp.asarray(toks)))
    got = np.asarray(sdar.forward(cfg, params, jnp.asarray(toks)[None])[0][0])
    assert np.abs(got - want).max() < TOL
    # a position sees its whole block: causal is another function, at a
    # hundred times the tolerance
    causal = np.asarray(sdar.forward(cfg, params, jnp.asarray(toks)[None],
                                     block=1)[0][0])
    assert np.abs(causal - want).max() > 100 * TOL
    # ... from the first block on: its first position sees the others
    assert np.abs(causal - want)[0].max() > 100 * TOL


def test_a_packed_row_of_three_prompts_equals_each_alone():
    cfg, params = model(4)
    lens, bs = (12, 5, 9), 8
    prompts = [_toks(n, seed=n) for n in lens]
    N = 40
    tokens, posn = np.zeros(N, np.int32), np.zeros(N, np.int32)
    seg, last, at = np.full(N, -1, np.int32), [], 0
    for i, p in enumerate(prompts):
        tokens[at:at + len(p)], seg[at:at + len(p)] = p, i
        posn[at:at + len(p)] = np.arange(len(p))
        last.append(at + len(p) - 1)
        at += -(-len(p) // bs) * bs
    packed = llama.Packed(jnp.asarray(last), jnp.asarray(seg),
                          jnp.asarray(posn))
    logits, (ks, vs) = sdar.forward(cfg, params, jnp.asarray(tokens)[None],
                                    packed=packed)
    assert logits.shape == (1, 3, cfg.vocab_size)
    none, (ks2, _) = sdar.forward(cfg, params, jnp.asarray(tokens)[None],
                                  packed=packed, logits=False)
    assert none is None and np.array_equal(np.asarray(ks), np.asarray(ks2))
    at = 0
    for i, p in enumerate(prompts):
        alone, (k1, v1) = sdar.forward(cfg, params, jnp.asarray(p)[None])
        assert np.abs(np.asarray(logits[0, i])
                      - np.asarray(alone[0, -1])).max() < TOL
        for a, b in ((ks, k1), (vs, v1)):
            assert np.abs(np.asarray(a[:, 0, at:at + len(p)])
                          - np.asarray(b[:, 0])).max() < TOL
        at += -(-len(p) // bs) * bs


def test_a_bfloat16_router_fails_the_tolerance():
    cfg, params = model(4)
    toks = _toks(32, seed=2)
    want = np.asarray(reference_logits(cfg, params, pad=8)(jnp.asarray(toks)))
    low = dict(params, layers={**params["layers"], "router": params[
        "layers"]["router"].astype(jnp.bfloat16).astype(jnp.float32)})
    got = np.asarray(sdar.forward(cfg, low, jnp.asarray(toks)[None])[0][0])
    assert np.abs(got - want).max() > 10 * TOL


# ---------------------------------------------------- the denoising choice
@pytest.mark.parametrize("B,S", [(4, 1), (4, 2), (4, 4), (8, 2), (8, 3),
                                 (8, 8)])
def test_unmask_is_the_references_choice(B, S):
    rng = np.random.default_rng(B * 10 + S)
    rows, V = 64, 12
    logits = rng.normal(size=(rows, B, V)).astype(np.float32) * 3.0
    # ties, and rows over the threshold
    logits[:8, 1] = logits[:8, 2]
    logits[8:24, :, 0] += 9.0
    und = rng.random((rows, B)) < 0.7
    und[:, 0] |= ~und.any(axis=1)
    s = rng.integers(0, S, size=rows)
    blk = rng.integers(1, V, size=(rows, B))
    dec = np.where(und, -1, 0)
    thr = np.where(np.arange(rows) % 3 == 0, 0.5, 0.9).astype(np.float32)
    nblk, nund, ndec = (np.asarray(a) for a in sdar.unmask(
        jnp.asarray(logits), jnp.asarray(blk, jnp.int32), jnp.asarray(und),
        jnp.asarray(dec, jnp.int32), jnp.asarray(s, jnp.int32),
        jnp.full((rows,), S, jnp.int32), jnp.asarray(thr)))
    x0, c = (np.asarray(a) for a in ref.confidence(jnp.asarray(logits)))
    both = 0
    for r in range(rows):
        chosen = ref.choose(c[r], und[r], ref.transfers(B, S, int(s[r])),
                            float(thr[r]))
        both += chosen.sum() > ref.transfers(B, S, int(s[r]))
        assert np.array_equal(nund[r], und[r] & ~chosen), r
        assert np.array_equal(nblk[r], np.where(chosen, x0[r], blk[r])), r
        assert np.array_equal(ndec[r], np.where(chosen, s[r], dec[r])), r
    # the threshold's branch decided more than `n_s` somewhere (`S` 1
    # decides the whole block either way)
    assert both > 0 or S == 1


# ------------------------------------------------- generate, step by step
def _cache(cfg, M, block_size, paged):
    """An empty cache of `M` rows for ONE sequence: the dense view `[L,
    1, M, KV * hd]`, or the folded pools with a scratch block behind and
    the table that names the others in order."""
    width = cfg.n_kv_heads * cfg.head_dim
    if not paged:
        k = jnp.zeros((cfg.n_layers, 1, M, width), cfg.dtype)
        return (k, k), None
    nb = M // block_size
    k = jnp.zeros((cfg.n_layers, nb + 1, block_size, width), cfg.dtype)
    return (k, k), jnp.arange(nb, dtype=jnp.int32)[None]


def _rows(cache):
    """A cache of `_cache` as rows `[L, M, KV * hd]` (k, v)."""
    if cache[0].shape[1] == 1:
        return tuple(np.asarray(c[:, 0]) for c in cache)
    return tuple(np.asarray(c[:, :-1]).reshape(c.shape[0], -1, c.shape[-1])
                 for c in cache)


def _loop(cfg, params, prompt, n, S, thr=0.9, block_size=8, fused=False,
          paged=False):
    """The engine's state machine by hand over `sdar.forward` (the
    prompt's whole blocks into the cache) and `sdar.block_step` /
    `sdar.unmask`: -> (answer, decided_at, forwards, [(pos, step,
    logits)] of the denoising forwards, the cache's rows).  `fused`
    False: a block decided is COMMITTED by a forward of its own, whose
    input has no mask.  True: it is output at once and its tokens ride
    as the commit half of the next forward (`block_step(commit=)`); the
    last block's never do.  `paged`: through the pools and the paged
    kernels in the interpreter."""
    B, T = cfg.block_length, len(prompt)
    end = -(-(T + n) // B) * B
    M = -(-end // block_size) * block_size
    (k, v), tables = _cache(cfg, M, block_size, paged)
    pos = T - T % B
    if pos:
        _, (ks, vs) = sdar.forward(cfg, params,
                                   jnp.asarray(prompt[:pos])[None],
                                   logits=False)
        if paged:
            k, v = (c.at[:, :-1].set(jnp.pad(
                new[:, 0], ((0, 0), (0, M - pos), (0, 0))).reshape(
                    c[:, :-1].shape)) for c, new in ((k, ks), (v, vs)))
        else:
            k, v = k.at[:, :, :pos].set(ks), v.at[:, :, :pos].set(vs)
    blk = np.array(list(prompt[pos:]) + [0] * (B - T % B))
    und, dec, s = np.arange(B) >= T % B, np.full(B, -1), 0
    held, pend = np.zeros(B, int), False
    kw = dict(tables=tables, interpret=paged)
    plain = jax.jit(lambda x, cache, p: sdar.block_step(
        cfg, params, x, cache, p, **kw))
    both = jax.jit(lambda x, cache, p, held, on: sdar.block_step(
        cfg, params, x, cache, p, commit=(held, on), **kw))
    out, decided, trace, forwards = [], [], [], 0
    while pos < end:
        x = jnp.asarray(np.where(und, cfg.mask_id, blk), jnp.int32)[None]
        at = jnp.asarray([pos], jnp.int32)
        if fused:
            logits, (k, v), _ = both(x, (k, v), at,
                                     jnp.asarray(held, jnp.int32)[None],
                                     jnp.asarray([pend]))
            pend = False
        else:
            logits, (k, v), _ = plain(x, (k, v), at)
        forwards += 1
        if und.any():
            trace.append((pos, s, np.asarray(logits[0])))
            nb, nu, nd = sdar.unmask(
                logits, jnp.asarray(blk, jnp.int32)[None],
                jnp.asarray(und)[None], jnp.asarray(dec, jnp.int32)[None],
                jnp.asarray([s], jnp.int32), jnp.asarray([S], jnp.int32),
                jnp.asarray([thr], jnp.float32))
            blk, und, dec, s = (np.asarray(nb[0]), np.asarray(nu[0]),
                                np.asarray(nd[0]), s + 1)
            if und.any() or not fused:
                continue
        out += [int(t) for t in blk]
        decided += [int(d) for d in dec]
        pos += B
        held, pend = blk, True
        blk, und, dec, s = (np.zeros(B, int), np.ones(B, bool),
                            np.full(B, -1), 0)
    lo = T % B
    return (out[lo:lo + n], decided[lo:lo + n], forwards, trace,
            _rows((k, v)))


@pytest.mark.parametrize("B,S,T,n", [(4, 1, 8, 8), (4, 2, 6, 7),
                                     (8, 2, 16, 16), (8, 8, 5, 11)])
def test_block_steps_equal_the_references_generate(B, S, T, n):
    """Logits of EVERY denoising forward within the tolerance, the
    tokens, the step each was decided at and the count of forwards
    equal: `T mod B` and `n mod B` of 0 and not, `S` of 1, 2 and `B`."""
    cfg, params = model(B)
    prompt = [int(t) for t in _toks(T, seed=T + n)]
    want = ref.generate(prompt, n, B, S, 0.9, cfg.mask_id,
                        reference_logits(cfg, params))
    got = _loop(cfg, params, prompt, n, S)
    assert got[0] == want[0] and got[1] == want[1] and got[2] == want[2]
    assert [(p, s) for p, s, _ in got[3]] == [(p, s) for p, s, _ in want[3]]
    for (_, _, a), (_, _, b) in zip(got[3], want[3]):
        assert np.abs(a - b).max() < TOL


@pytest.mark.parametrize("paged", [False, True], ids=["view", "paged"])
@pytest.mark.parametrize("B,S,T,n", [(4, 1, 8, 12), (4, 2, 6, 11),
                                     (4, 4, 5, 9), (8, 2, 16, 16)])
def test_a_commit_that_rides_is_a_commit_forward_of_its_own(B, S, T, n,
                                                           paged):
    """A block's clean rows written by the NEXT block's first forward
    (`block_step(commit=)`) against a commit forward followed by a
    separate first forward: every denoising forward's logits (each next
    block's first reads the rows the commit half wrote in the same
    layers) and the rows left in the cache within the tolerance, the
    same tokens at the same steps, one forward a block fewer; `S` of 1,
    2 and `B`, `T mod B` of 0 and not, the view and the paged kernels in
    the interpreter.  The LAST block is never committed: its rows stay
    as a forward with masks in its input wrote them."""
    cfg, params = model(B)
    prompt = [int(t) for t in _toks(T, seed=T + n + S)]
    two = _loop(cfg, params, prompt, n, S, paged=paged)
    one = _loop(cfg, params, prompt, n, S, fused=True, paged=paged)
    assert one[:2] == two[:2]
    blocks = len({pos for pos, _, _ in two[3]})
    assert one[2] == two[2] - blocks == len(one[3]) and blocks >= 2
    assert [(p, s) for p, s, _ in one[3]] == [(p, s) for p, s, _ in two[3]]
    for (_, _, a), (_, _, b) in zip(one[3], two[3]):
        assert np.abs(a - b).max() < TOL
    last = max(pos for pos, _, _ in two[3])
    for a, b in zip(one[4], two[4]):
        assert np.abs(a[:, :last] - b[:, :last]).max() < TOL
        assert np.abs(a[:, last:last + B] - b[:, last:last + B]).max() > TOL


@pytest.mark.parametrize("paged", [False, True], ids=["view", "paged"])
def test_a_commit_half_that_does_not_ride_writes_nothing(paged):
    """Three slots' forward with a commit half a slot of which NONE
    rides (one slot at position 0, with no block behind it; one dead):
    every row of the cache but the live rows' open blocks is BIT-equal
    before and after, and the logits are the narrow forward's."""
    B, bs, M = 4, 8, 24
    cfg, params = model(B)
    width = cfg.n_kv_heads * cfg.head_dim
    rng = np.random.default_rng(7)
    if paged:
        shape = (cfg.n_layers, 3 * M // bs + 1, bs, width)
        tables = jnp.arange(3 * M // bs, dtype=jnp.int32).reshape(3, -1)
    else:
        shape, tables = (cfg.n_layers, 3, M, width), None
    cache = tuple(jnp.asarray(rng.normal(size=shape), cfg.dtype)
                  for _ in range(2))
    pos = jnp.asarray([8, 0, 12], jnp.int32)
    live = jnp.asarray([True, True, False])
    x = jnp.asarray(rng.integers(1, 200, size=(3, B)), jnp.int32)
    held = jnp.asarray(rng.integers(1, 200, size=(3, B)), jnp.int32)
    kw = dict(tables=tables, live=live, interpret=paged)
    narrow, _, _ = sdar.block_step(cfg, params, x, cache, pos, **kw)
    logits, after, _ = sdar.block_step(
        cfg, params, x, cache, pos, commit=(held, jnp.zeros((3,), bool)),
        **kw)
    assert np.abs(np.asarray(logits) - np.asarray(narrow))[:2].max() < TOL
    for was, now in zip(cache, after):
        was, now = (np.asarray(c).reshape(cfg.n_layers, -1, width)[:, :3 * M]
                    .reshape(cfg.n_layers, 3, M, width) for c in (was, now))
        wrote = np.zeros((3, M), bool)
        wrote[0, 8:12] = wrote[1, 0:4] = True
        assert np.array_equal(was[:, ~wrote], now[:, ~wrote])
        assert not np.array_equal(was[:, wrote], now[:, wrote])
    # ... and one that rides writes its block's rows, and only those
    _, rode, _ = sdar.block_step(
        cfg, params, x, cache, pos,
        commit=(held, jnp.asarray([True, False, False])), **kw)
    for now, then in zip(after, rode):
        now, then = (np.asarray(c).reshape(cfg.n_layers, -1, width)[:, :3 * M]
                     .reshape(cfg.n_layers, 3, M, width) for c in (now, then))
        behind = np.zeros((3, M), bool)
        behind[0, 4:8] = True
        rest = ~behind & ~wrote
        assert np.array_equal(now[:, rest], then[:, rest])
        assert not np.array_equal(now[:, behind], then[:, behind])


def test_replay_gives_generates_logits_at_every_block_and_step():
    """The teacher-forced replay (the sequence laid out clean and noisy,
    one forward a step index) against `generate`'s own forwards."""
    B, S, T, n = 4, 4, 6, 10      # ragged prompt, the answer ends a block
    cfg, params = model(B)
    prompt = [int(t) for t in _toks(T, seed=9)]
    answer, dec, _, trace = ref.generate(
        prompt, n, B, S, 0.9, cfg.mask_id, reference_logits(cfg, params))
    ends, layers, kw = parts(cfg, params)
    again = np.asarray(ref.replay(prompt, answer, dec, B, cfg.mask_id, ends,
                                  layers, kw))
    assert again.shape[0] == max(dec) + 1
    seen = 0
    for pos, s, logits in trace:
        assert np.abs(again[s, pos:pos + B] - logits).max() < TOL
        seen += 1
    assert seen == len(trace) >= 3 * S - 2
    # the margins a sound run reads: the served token IS the reference's
    # choice at its own step, and the choices agree
    lg = np.stack([again[d, T + i] for i, d in enumerate(dec)])
    assert float(np.asarray(ref.margins(jnp.asarray(lg),
                                        jnp.asarray(answer))).max()) == 0.0
    conf = np.stack([np.asarray(ref.confidence(jnp.asarray(a[T:T + n]))[1])
                     for a in again])
    marg = ref.choice_margin(conf, dec, B, S, T)
    assert marg and max(marg) == 0.0
    # another answer's choices do not agree
    wrong = [dec[1], dec[0]] + list(dec[2:])
    assert wrong != dec and max(ref.choice_margin(conf, wrong, B, S, T)) > 0.0
