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
        # beside each forward: which rows carried a pending commit
        self.riding = []

    def __call__(self, cfg, params, tokens, cache, pos, **kw):
        logits, cache, stats = self._real(cfg, params, tokens, cache, pos,
                                          **kw)
        commit = kw.get("commit")
        riding = np.zeros(pos.shape, bool) if commit is None else commit[1]

        def keep(p, l, lg, r):
            self.forwards.append((np.asarray(p), np.asarray(l),
                                  np.asarray(lg)))
            self.riding.append(np.asarray(r))

        jax.debug.callback(keep, pos, kw["live"], logits, riding,
                           ordered=True)
        return logits, cache, stats

    def clear(self):
        self.forwards.clear()
        self.riding.clear()

    def of_the_one_live_row(self):
        """[(pos, logits [B, vocab])] of the forwards in which exactly
        one row was live (a request served alone), then forgotten."""
        out = [(int(p[l][0]), lg[l][0]) for p, l, lg in self.forwards
               if l.sum() == 1]
        self.clear()
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


def _forwards(want):
    """The forwards the ENGINE takes for the reference's answer: the
    reference counts one commit a block (`benchmarks/reference/sdar.py`,
    which a serving PR does not edit) and the engine's commit rides in
    the next block's first forward, so its count is the reference's
    less its blocks: the denoising forwards alone."""
    blocks = len({pos for pos, _, _ in want[3]})
    assert want[2] - blocks == len(want[3])
    return want[2] - blocks


def _same(out, want):
    assert isinstance(out, Generated)
    assert list(out) == want[0] and out.decided_at == want[1]
    assert out.forwards == _forwards(want)


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
    rec.clear()
    out = eng.submit(prompt, n, denoising_steps=S).result(timeout=300)
    _same(out, want)
    time.sleep(0.2)   # the chunk that ran behind the last harvest
    mine = rec.of_the_one_live_row()
    # every forward is a denoising forward: a block's commit rides in
    # the next block's first, whose logits read the clean rows it wrote
    assert len(mine) == _forwards(want) == len(want[3])
    for (pos, logits), (p, _, wanted) in zip(mine, want[3]):
        assert p == pos and np.abs(logits - wanted).max() < TOL
    record = eng.stats()["request_ring"][-1]
    assert (record["denoising_steps"], record["forwards"],
            record["tokens_out"]) == (S, _forwards(want), n)


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
    rec.clear()
    ticks = [t for t in eng.stats()["tick_ring"] if t.get("row_steps_live")]
    assert ticks
    for t in ticks:
        assert t["row_steps_live"] == (t["commit_row_steps"]
                                       + t["denoise_row_steps"])
        assert t["commit_row_steps"] == 0 and t["tokens_committed"] % B == 0
        assert 0 <= t["fused_commit_row_steps"] <= t["denoise_row_steps"]
        assert t["row_steps"] == ENGINE["slots"] * ENGINE["chunk"]
        assert 0 < t["experts_touched"] <= t["experts_total"]


def _ticks_since(eng, seq):
    time.sleep(0.3)   # the tick that harvested the last chunk closes
    return [t for t in eng.stats()["tick_ring"]
            if t["seq"] > seq and t.get("row_steps_live")]


def test_the_tick_ring_counts_what_both_halves_did(served):
    """Three requests at once: no live row-forward decides nothing
    (`commit_row_steps` 0), `tokens_committed` is the tokens OUTPUT,
    `fused_commit_row_steps` one a block but a request's first, and
    `attended_tokens` (the columns ONE CALL of the attention kernel
    read, a forward's mean: both halves', halved) sums to a hand count
    from the reference's own trace."""
    cfg, eng, rec, logits_of = served
    B, chunk = cfg.block_length, ENGINE["chunk"]
    cases = [(9, 39, 1), (12, 36, 2), (7, 41, B)]
    prompts = [[int(t) for t in _toks(T, seed=T + 90)] for T, _, _ in cases]
    wants = [_want(cfg, logits_of, p, n, S)
             for p, (_, n, S) in zip(prompts, cases)]
    before = eng.stats()
    futs = [eng.submit(p, n, denoising_steps=S)
            for p, (_, n, S) in zip(prompts, cases)]
    for f, want in zip(futs, wants):
        _same(f.result(timeout=300), want)
    ticks = _ticks_since(eng, before["ticks"])
    for t in ticks:
        assert t["commit_row_steps"] == 0
        assert t["row_steps_live"] == t["denoise_row_steps"]
        assert 0 <= t["fused_commit_row_steps"] <= t["denoise_row_steps"]
    traces = [want[3] for want in wants]
    firsts = [[pos for pos, s, _ in tr if s == 0] for tr in traces]
    total = lambda k: sum(t[k] for t in ticks)               # noqa: E731
    assert total("denoise_row_steps") == sum(len(tr) for tr in traces)
    assert total("tokens_committed") == B * sum(len(f) for f in firsts)
    # a block's clean rows ride in the NEXT block's first forward
    fused = sum(len(f) - 1 for f in firsts)
    assert 0 < fused == total("fused_commit_row_steps")
    assert (eng.stats()["fused_commit_row_steps"]
            - before["fused_commit_row_steps"]) == fused
    # an open half attends `pos + B` columns, a commit half `pos`
    columns = (sum(pos + B for tr in traces for pos, _, _ in tr)
               + sum(sum(f[1:]) for f in firsts))
    assert round(total("attended_tokens") * chunk * 2) == columns
    rec.clear()


def test_a_pending_commit_waits_over_a_chunks_end_and_an_admission(served):
    """`S` = `chunk` forwards a block: every block is decided by a
    chunk's LAST forward, so its commit waits in the row's state for
    the next program.  Another request is admitted in between: the
    first's commit rides in the first forward of the chunk that holds
    the second's first forward, and both equal their references."""
    cfg, eng, rec, logits_of = served
    chunk = ENGINE["chunk"]
    cases = [(8, 48, chunk), (6, 10, 1)]
    prompts = [[int(t) for t in _toks(T, seed=T + 70)] for T, _, _ in cases]
    wants = [_want(cfg, logits_of, p, n, S)
             for p, (_, n, S) in zip(prompts, cases)]
    rec.clear()
    long_ = eng.submit(prompts[0], cases[0][1], denoising_steps=chunk)
    while not eng.stats()["ticks"] or not rec.forwards:
        time.sleep(0.001)   # the long one is being served
    short = eng.submit(prompts[1], cases[1][1], denoising_steps=1)
    _same(long_.result(timeout=300), wants[0])
    _same(short.result(timeout=300), wants[1])
    time.sleep(0.2)
    live = np.stack([l for _, l, _ in rec.forwards])
    riding = np.stack(rec.riding)
    assert len(live) % chunk == 0
    a = int(np.argmax(live[0]))                  # the long one's slot
    b = int(np.argmax(live.any(axis=0) & (np.arange(live.shape[1]) != a)))
    # over every chunk's end: the commit rides in the next one's first
    starts = np.arange(chunk, len(live), chunk)
    starts = starts[live[starts, a]]
    assert len(starts) >= 4 and riding[starts, a].all()
    assert not riding[starts + 1, a].any()       # ... and only there
    # the chunk in which the second request's row first ran
    born = int(np.argmax(live[:, b])) // chunk * chunk
    assert born in starts and riding[born, a] and not riding[born, b]
    rec.clear()


def test_the_last_block_is_output_and_never_committed(served):
    """A request alone: a commit rides once a block but the last, whose
    rows nobody reads.  The flag it leaves in the dead row's state is
    not the next tenant's: that row's first forward carries nothing,
    and its answer is its reference's."""
    cfg, eng, rec, logits_of = served
    B, tenants = cfg.block_length, set()
    for T, n, S in ((8, 16, 2), (5, 9, 1)):
        prompt = [int(t) for t in _toks(T, seed=T + 60)]
        want = _want(cfg, logits_of, prompt, n, S)
        blocks = len({pos for pos, _, _ in want[3]})
        rec.clear()
        _same(eng.submit(prompt, n, denoising_steps=S).result(timeout=300),
              want)
        time.sleep(0.2)
        mine = [(int(np.argmax(l)), int(p[l][0]), bool(r[l][0]))
                for (p, l, _), r in zip(rec.forwards, rec.riding)
                if l.sum() == 1]
        tenants.add(mine[0][0])
        assert len({slot for slot, _, _ in mine}) == 1 == len(tenants)
        rode = [pos for _, pos, r in mine if r]
        first = (T - T % B)
        # at each block's first forward but the request's first: the
        # last block (at `first + (blocks - 1) B`) is where the last rode
        assert rode == [first + B * i for i in range(1, blocks)]
        assert not mine[0][2] and sum(r.sum() for r in rec.riding) == len(rode)
    rec.clear()


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
    rec.clear()


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
            assert out.forwards == sum(steps) < 6 * S
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
        assert (one["forwards"], four["forwards"]) == ([2], [8])
        for body, S in ((one, 1), (four, 4)):
            assert body["tokens"][0] == _want(cfg, logits_of, prompt, 8, S)[0]
        assert post()["forwards"] == [8]        # the config's default
    finally:
        serve.delete("blocks")
        rt.shutdown()
