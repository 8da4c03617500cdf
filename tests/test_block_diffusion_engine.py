"""The engine's block-diffusion path (`BlockDiffusionEngineModel`, the
block chunk program, a harvest that takes the device's word) against the
plain reference's `generate` on seeded weights at tiny sizes: logits of
every forward, tokens, the step each was decided at, and the count of
forwards, which the host learns from the device.  Tolerance: `TOL` of
`test_sdar_model.py` (float32 on both sides), with its reason there.
"""

import time
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from benchmarks.reference import sdar as ref
from ray_tpu.exceptions import PrefixCacheUnsupportedError
from ray_tpu.models import llama, sdar
from ray_tpu.serve import engine_model
from ray_tpu.serve.llm_engine import Generated, LlamaEngine
from test_sdar_model import TOL, _toks, model, reference_logits

ENGINE = dict(slots=4, chunk=4, block_size=8, max_len=64, kv_blocks=28)


class Recorder:
    """`sdar.block_step` with every forward's `(pos, live, logits)`
    handed to the host: what the engine computed, not only what it
    picked."""

    def __init__(self):
        self.forwards, self._real = [], sdar.block_step

    def __call__(self, cfg, params, tokens, cache, pos, **kw):
        logits, cache, stats = self._real(cfg, params, tokens, cache, pos,
                                          **kw)
        jax.debug.callback(
            lambda p, l, lg: self.forwards.append(
                (np.asarray(p), np.asarray(l), np.asarray(lg))),
            pos, kw["live"], logits, ordered=True)
        return logits, cache, stats

    def of_the_one_live_row(self):
        """[(pos, logits [B, vocab])] of the forwards in which exactly
        one row was live (a request served alone), then forgotten."""
        out = [(int(p[l][0]), lg[l][0]) for p, l, lg in self.forwards
               if l.sum() == 1]
        self.forwards.clear()
        return out


@pytest.fixture(scope="module", params=[4, 8], ids=["B4", "B8"])
def served(request):
    """(cfg, engine, recorder, `logits_of` of the reference) at a block
    length; the engine's programs traced with the recorder inside."""
    B = request.param
    cfg, params = model(B)
    rec = Recorder()
    sdar.block_step = rec
    try:
        eng = LlamaEngine(cfg, params, **ENGINE)
        yield cfg, eng, rec, reference_logits(cfg, params)
        eng.shutdown()
    finally:
        sdar.block_step = rec._real


def _want(cfg, logits_of, prompt, n, S, thr=0.9):
    return ref.generate(prompt, n, cfg.block_length, S, thr, cfg.mask_id,
                        logits_of)


def _same(out, want):
    assert isinstance(out, Generated)
    assert list(out) == want[0] and out.decided_at == want[1]
    assert out.forwards == want[2]


@pytest.mark.parametrize("T,n,S", [(8, 8, 1), (6, 7, 2), (13, 9, 0),
                                   (16, 16, 2), (5, 11, 0)])
def test_a_request_alone_equals_the_reference_forward_by_forward(served, T,
                                                                 n, S):
    """`S` of 1, 2 and `B` (0 here), `T mod B` and `n mod B` of 0 and
    not: logits of EVERY denoising forward within the tolerance; tokens,
    steps and the count of forwards equal."""
    cfg, eng, rec, logits_of = served
    S = S or cfg.block_length
    prompt = [int(t) for t in _toks(T, seed=T * 31 + n)]
    want = _want(cfg, logits_of, prompt, n, S)
    rec.forwards.clear()
    out = eng.submit(prompt, n, denoising_steps=S).result(timeout=300)
    _same(out, want)
    time.sleep(0.2)   # the chunk that ran behind the last harvest
    mine = rec.of_the_one_live_row()
    assert len(mine) == want[2]
    trace = iter(want[3])
    at, left = None, 0
    for pos, logits in mine:
        if pos != at:   # a new block: its denoising forwards, then one more
            at, left = pos, sum(1 for p, _, _ in want[3] if p == pos)
        if left:
            p, _, wanted = next(trace)
            assert p == pos and np.abs(logits - wanted).max() < TOL
            left -= 1
    assert next(trace, None) is None
    record = eng.stats()["request_ring"][-1]
    assert (record["denoising_steps"], record["forwards"],
            record["tokens_out"]) == (S, want[2], n)


def test_rows_that_commit_and_rows_that_denoise_share_a_forward(served):
    """Three requests of different steps and lengths in flight at once,
    one admitted while the others are mid-chunk, a fourth slot never
    used (a dead row): each equals its own reference, and the tick ring
    counts ROW-FORWARDS by kind, from the device."""
    cfg, eng, rec, logits_of = served
    B = cfg.block_length
    cases = [(9, 39, 1), (12, 36, 2), (7, 41, B)]
    prompts = [[int(t) for t in _toks(T, seed=T)] for T, _, _ in cases]
    futs = [eng.submit(p, n, denoising_steps=S)
            for p, (_, n, S) in zip(prompts[:2], cases)]
    time.sleep(0.05)
    futs.append(eng.submit(prompts[2], cases[2][1],
                           denoising_steps=cases[2][2]))
    for p, (_, n, S), f in zip(prompts, cases, futs):
        _same(f.result(timeout=300), _want(cfg, logits_of, p, n, S))
    mixed = [(l.sum(), len({int(x) for x in p[l]}))
             for p, l, _ in rec.forwards]
    assert max(live for live, _ in mixed) == 3
    ever = np.any([l for _, l, _ in rec.forwards], axis=0)
    assert ever.sum() == 3                   # the fourth slot: a dead row
    rec.forwards.clear()
    ticks = [t for t in eng.stats()["tick_ring"] if t.get("row_steps_live")]
    assert ticks
    for t in ticks:
        assert t["row_steps_live"] == (t["commit_row_steps"]
                                       + t["denoise_row_steps"])
        assert t["tokens_committed"] == B * t["commit_row_steps"]
        assert t["row_steps"] == ENGINE["slots"] * ENGINE["chunk"]
        assert 0 < t["experts_touched"] <= t["experts_total"]


def test_three_prompts_packed_into_one_admission(served):
    cfg, eng, rec, logits_of = served
    cases = [(12, 8, 2), (5, 7, 1), (9, 4, cfg.block_length)]
    prompts = [[int(t) for t in _toks(T, seed=T + 50)] for T, _, _ in cases]
    entries = [(p, n, Future(), time.time(), None, None,
                eng._model.request_fields(S, None))
               for p, (_, n, S) in zip(prompts, cases)]
    calls = eng.stats()["prefill_calls"]
    with eng._wake:   # one tick's admissions, as `test_packed_prefill`
        eng._queue.extend(entries)
        eng._wake.notify()
    for p, (_, n, S), e in zip(prompts, cases, entries):
        _same(e[2].result(timeout=300), _want(cfg, logits_of, p, n, S))
    assert eng.stats()["prefill_calls"] == calls + 1
    assert {r["prefill_rows"] for r in
            eng.stats()["request_ring"][-3:]} == {3}
    rec.forwards.clear()


def test_the_threshold_ends_blocks_early_and_the_device_says_so():
    """A small vocabulary and a large init: confidences straddle the
    threshold, so some blocks take fewer than `S` denoising forwards.
    The engine's count equals the reference's, and the host has it from
    the device alone: its own mirror only bounds `pos`."""
    B, S = 4, 4
    cfg, params = model(B, seed=3, vocab_size=12, mask_id=11)
    params = jax.tree.map(lambda p: p * 4.0 if p.ndim > 1 else p, params)
    logits_of = reference_logits(cfg, params)
    eng = LlamaEngine(cfg, params, **ENGINE)
    try:
        early = late = 0
        for seed, thr in ((1, 0.5), (2, 0.5), (3, 0.35)):
            prompt = [int(t) for t in _toks(8, seed=seed, vocab=11)]
            want = _want(cfg, logits_of, prompt, 24, S, thr)
            out = eng.submit(prompt, 24, denoising_steps=S,
                             confidence_threshold=thr).result(timeout=300)
            _same(out, want)
            steps = [sum(1 for p, _, _ in want[3] if p == pos)
                     for pos in range(8, 32, B)]
            early += sum(k < S for k in steps)
            late += sum(k > 1 for k in steps)
            assert out.forwards == sum(steps) + len(steps) < 6 * (S + 1)
        assert early and late          # it bites, and not everywhere
    finally:
        eng.shutdown()


def test_a_block_left_uncommitted_is_another_model():
    """`commit` False (the benchmark's control): a block's rows stay as
    its last denoising forward wrote them, masks in its input.  The next
    block's logits then miss the reference by far more than rounding."""
    cfg, params = model(4)
    logits_of = reference_logits(cfg, params)
    prompt = [int(t) for t in _toks(8, seed=4)]
    want = _want(cfg, logits_of, prompt, 12, 2)
    rec = Recorder()
    sdar.block_step = rec
    engine_model.BlockDiffusionEngineModel.commit = False
    try:
        eng = LlamaEngine(cfg, params, **ENGINE)
        assert eng._model.advance == ENGINE["chunk"] * 4
        out = eng.submit(prompt, 12, denoising_steps=2).result(timeout=300)
        eng.shutdown()
    finally:
        engine_model.BlockDiffusionEngineModel.commit = True
        sdar.block_step = rec._real
    assert len(out) == 12 and out.forwards == 3 * 2       # no commits
    mine = rec.of_the_one_live_row()
    first = [lg for pos, lg in mine if pos == 8]
    second = [lg for pos, lg in mine if pos == 12]
    wanted = {pos: [lg for p, _, lg in want[3] if p == pos]
              for pos in (8, 12)}
    assert np.abs(first[0] - wanted[8][0]).max() < TOL    # nothing to miss
    assert np.abs(second[0] - wanted[12][0]).max() > 100 * TOL


def test_the_paged_kernels_serve_the_same_tokens():
    """The kernel route in the interpreter (`B` rows appended a slot,
    `B x H` query heads of one row) against the reference."""
    cfg, params = model(4)
    logits_of = reference_logits(cfg, params)
    eng = LlamaEngine(cfg, params, decode_kernel="pallas",
                      kernel_interpret=True, **ENGINE)
    try:
        cases = [(6, 10, 2), (8, 8, 4)]
        prompts = [[int(t) for t in _toks(T, seed=T + 7)] for T, _, _ in cases]
        futs = [eng.submit(p, n, denoising_steps=S)
                for p, (_, n, S) in zip(prompts, cases)]
        for p, (_, n, S), f in zip(prompts, cases, futs):
            _same(f.result(timeout=600), _want(cfg, logits_of, p, n, S))
        assert eng.stats()["decode_kernel"] == "pallas"
    finally:
        eng.shutdown()


def test_what_the_seam_refuses():
    cfg, params = model(4)
    kw = dict(kv_dtype="model", block_size=8, chunk=4, paged=False,
              interpret=False)
    with pytest.raises(ValueError, match="int8"):
        engine_model.engine_model_for(cfg, **{**kw, "kv_dtype": "int8"})
    with pytest.raises(ValueError, match="whole blocks"):
        engine_model.engine_model_for(cfg, **{**kw, "block_size": 6})
    with pytest.raises(PrefixCacheUnsupportedError):
        LlamaEngine(cfg, params, prefix_cache=True, **ENGINE)
    with pytest.raises(ValueError, match="max_len"):
        LlamaEngine(cfg, params, **{**ENGINE, "max_len": 62})
    m = engine_model.engine_model_for(cfg, **kw)
    assert m.request_fields() == {"denoising_steps": 4,
                                  "confidence_threshold": 0.9}
    assert (m.rows_needed(6, 7), m.first_pos(6), m.rows_needed(8, 8)) == (
        16, 4, 16)
    for bad in (0, 5):
        with pytest.raises(ValueError, match="denoising_steps"):
            m.request_fields(bad)
    # a model that yields a token a step takes neither field: the
    # request is refused as a prompt it cannot hold is
    lcfg = llama.LlamaConfig.tiny()
    eng = LlamaEngine(lcfg, llama.init_params(lcfg, jax.random.PRNGKey(0)),
                      slots=2, chunk=2, block_size=8, max_len=32)
    try:
        for fields in ({"denoising_steps": 2}, {"confidence_threshold": .5}):
            with pytest.raises(ValueError, match="one token a step"):
                eng.submit([1, 2, 3], 4, **fields).result(timeout=60)
        got = eng.submit([1, 2, 3], 4).result(timeout=300)
        assert len(got) == 4 and not isinstance(got, Generated)
        assert "forwards" not in eng.stats()["request_ring"][-1]
    finally:
        eng.shutdown()


def test_the_deployment_passes_the_bodys_fields():
    """`serve.run` -> proxy -> router -> replica -> `submit(...,
    denoising_steps=...)`: two requests that differ in the body's field
    alone differ in the forwards they took."""
    import json
    import urllib.request

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.examples.serve_llm import ContinuousLlamaService, _build_model

    cfg, params = _build_model("sdar_tiny", seed=0)
    logits_of = reference_logits(cfg, params)
    prompt = [int(t) for t in _toks(8, seed=11)]
    rt.init(num_workers=3, num_cpus=8, ignore_reinit_error=True)
    try:
        serve.run(ContinuousLlamaService.options(
            health_check_timeout_s=120).bind(
                model_size="sdar_tiny", max_new_tokens=8, slots=2, chunk=4,
                max_len=48, block_size=8, prefix_cache=False,
                jax_platform="cpu"),
            name="blocks", route_prefix="/blocks", timeout_s=300.0)
        host, port = serve.http_address()

        def post(**fields):
            req = urllib.request.Request(
                f"http://{host}:{port}/blocks", method="POST",
                data=json.dumps({"tokens": [prompt], "max_new_tokens": 8,
                                 **fields}).encode())
            with urllib.request.urlopen(req, timeout=300) as r:
                return json.loads(r.read())

        one, four = post(denoising_steps=1), post(denoising_steps=4)
        assert (one["forwards"], four["forwards"]) == ([4], [10])
        for body, S in ((one, 1), (four, 4)):
            assert body["tokens"][0] == _want(cfg, logits_of, prompt, 8, S)[0]
        assert post()["forwards"] == [10]       # the config's default
    finally:
        serve.delete("blocks")
        rt.shutdown()
