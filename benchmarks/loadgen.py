"""The one general traffic generator, and the client that offers it.

A traffic mix is a data file (`benchmarks/traffic/<mix>.json`); this
module turns it and `--seed` into requests and sends them over HTTP
from one thread.  The work is fixed by the mix, not by the seed: the
sizes and the gaps between arrivals, and their order, are drawn from
the mix's own `mix_seed`; `--seed` fills in the token ids (and, in the
planes, the weights).  With `"seed_reorders": true` in the mix the seed
also shuffles sizes and gaps: the same work in another order.  That is
off in the cells' mixes because the order decides which requests meet
at the end of the window: reordering moved `request_p95_ms` by +-2.2%
and the tokens completed in the window by +-2.4% from seed to seed,
while two runs of one seed agree within 0.7% (chip, PR 24).

Kinds:
- `open_loop`: arrivals on a schedule at `rate_per_s` whether or not
  earlier requests have finished; gaps are gamma-distributed with
  coefficient of variation `arrival_cv` (1 = Poisson).  A request is
  timed from the instant it was DUE, and how late it was sent is kept.
- `closed_loop`: `clients` callers, each sending its next request when
  the previous one returns, until the window's end; the requests out
  then are waited for (`drain_s` is the ceiling of that wait).  Its
  rate is read as tokens are produced (`produced_per_s`), not from the
  answers that happened to end inside the window.
- `train_stream`: no requests; the train plane reads the batch shape
  and token distribution from the same file.

Lengths: `{"fixed": n}`, `{"choices": [...], "weights": [...]}`, or
`{"dist": "lognormal", "median": m, "sigma": s}`; with a `grid`
(`min`, `max`, `step`) a drawn length is rounded UP to the grid and
clipped to it, so the engine sees a closed set of shapes.

Fields that differ by request: `"request_fields": {name: spec}`, each
spec `{"fixed": v}` or `{"choices": [...], "weights": [...]}` with the
values as the file writes them (a number, a string).  A request's
fields go into its body beside `tokens` and `max_new_tokens`, which no
field may be called.  Each field is drawn once a request from a
generator of its own, seeded from `mix_seed` and the field's name: the
lengths, gaps and prompts of a mix are what they are without the key,
and a field added later moves no other.  Under `seed_reorders` a
request's fields move with its lengths.  A mix without the key sends
the bytes it always sent.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import time
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    due_s: float            # open loop: offset from the window's start
    prompt: List[int]
    n_out: int
    client: int = -1        # closed loop: which caller sends it
    fields: dict = dataclasses.field(default_factory=dict)  # `request_fields`


@dataclasses.dataclass
class Record:
    idx: int
    due_s: float
    sent_s: float = math.nan
    done_s: float = math.nan
    ok: bool = False
    status: int = 0
    prompt_len: int = 0
    want: int = 0
    got: int = 0
    engine_s: float = math.nan   # measured by the deployment around submit
    replica: str = ""
    error: str = ""
    cut: bool = False            # closed loop: still in flight at the end
    fields: dict = dataclasses.field(default_factory=dict)


# ----------------------------------------------------------------------
# sizes and schedules (pure: no clock, no I/O)
# ----------------------------------------------------------------------
def grid_values(grid: dict) -> List[int]:
    return list(range(int(grid["min"]), int(grid["max"]) + 1,
                      int(grid["step"])))


def _choice_p(spec: dict) -> np.ndarray:
    w = np.asarray(spec.get("weights", [1] * len(spec["choices"])), float)
    return w / w.sum()


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if "fixed" in spec:
        out = np.full(n, int(spec["fixed"]), np.int64)
    elif "choices" in spec:
        out = rng.choice(np.asarray(spec["choices"], np.int64), size=n,
                         p=_choice_p(spec))
    elif spec.get("dist") == "lognormal":
        out = np.rint(np.exp(rng.normal(math.log(spec["median"]),
                                        spec["sigma"], size=n))).astype(np.int64)
    else:
        raise ValueError(f"unknown length spec {spec!r}")
    grid = spec.get("grid")
    if grid:
        step, lo, hi = int(grid["step"]), int(grid["min"]), int(grid["max"])
        out = lo + -(-(np.maximum(out, lo) - lo) // step) * step  # round UP
        out = np.minimum(out, hi)
    return np.maximum(out, 1)


def possible_lengths(spec: dict) -> List[int]:
    """Every length a spec can produce — what set-up has to warm.  A
    lognormal without a grid has no closed set and is refused."""
    if "fixed" in spec:
        return [int(spec["fixed"])]
    if "choices" in spec:
        return sorted(int(c) for c in spec["choices"])
    if spec.get("grid"):
        return grid_values(spec["grid"])
    raise ValueError("a drawn length needs a `grid`: continuous lengths "
                     "would compile inside the measured window")


RESERVED_FIELDS = ("tokens", "max_new_tokens")


def request_field_specs(mix: dict) -> dict:
    """The mix's `request_fields`, refused where a field takes a name
    the body already has or a spec of no known form."""
    specs = mix.get("request_fields") or {}
    for name, spec in specs.items():
        if name in RESERVED_FIELDS:
            raise ValueError(f"`request_fields` may not name {name!r}: the "
                             "body carries it already")
        if not isinstance(spec, dict) or not ("fixed" in spec
                                              or spec.get("choices")):
            raise ValueError(f"unknown field spec for {name!r}: {spec!r}")
    return specs


def draw_fields(mix: dict, n: int) -> List[dict]:
    """One dict of fields a request, from the mix's own seed alone."""
    specs = request_field_specs(mix)
    cols = {}
    for name, spec in specs.items():
        if "fixed" in spec:
            cols[name] = [spec["fixed"]] * n
            continue
        rng = np.random.default_rng(
            [int(mix.get("mix_seed", 0)), 0xF1E1D, *name.encode()])
        picks = rng.choice(len(spec["choices"]), size=n, p=_choice_p(spec))
        cols[name] = [spec["choices"][i] for i in picks]
    return [{name: col[i] for name, col in cols.items()} for i in range(n)]


def _tokens(rng, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, size=n).tolist()


def _prompts(mix: dict, plens, rng, vocab: int) -> List[List[int]]:
    """Unshared random prompts, or — with `shared_prefix` — `groups`
    prefixes of `len` tokens that requests pick at random."""
    sp = mix.get("shared_prefix") or {}
    groups = [_tokens(rng, int(sp["len"]), vocab)
              for _ in range(int(sp.get("groups", 0)))]
    out = []
    for n in plens:
        n = int(n)
        if groups:
            g = groups[int(rng.integers(len(groups)))]
            keep = min(len(g), max(0, n - int(sp.get("min_suffix", 16))))
            out.append(g[:keep] + _tokens(rng, n - keep, vocab))
        else:
            out.append(_tokens(rng, n, vocab))
    return out


def open_loop_schedule(mix: dict, seconds: float, seed: int,
                       vocab: int) -> List[Request]:
    rate, cv = float(mix["rate_per_s"]), float(mix.get("arrival_cv", 1.0))
    fixed = np.random.default_rng(int(mix.get("mix_seed", 0)))
    shape = 1.0 / (cv * cv)
    gaps = []
    total = 0.0
    while True:  # the mix's own draw: same gaps and sizes for every seed
        g = float(fixed.gamma(shape, 1.0 / (rate * shape)))
        if total + g > seconds:
            break
        total += g
        gaps.append(g)
    n = len(gaps)
    plens = draw_lengths(mix["prompt_len"], n, fixed)
    olens = draw_lengths(mix["output_len"], n, fixed)
    fields = draw_fields(mix, n)
    rng = np.random.default_rng([int(seed), 0x5EED])
    gaps = np.asarray(gaps)
    if mix.get("seed_reorders"):
        gaps = rng.permutation(gaps)
        order = rng.permutation(n)
        plens, olens = plens[order], olens[order]
        fields = [fields[i] for i in order]
    due = np.cumsum(gaps)
    prompts = _prompts(mix, plens, rng, vocab)
    return [Request(i, float(due[i]), prompts[i], int(olens[i]),
                    fields=fields[i]) for i in range(n)]


def closed_loop_schedule(mix: dict, seed: int, vocab: int) -> List[List[Request]]:
    """Per client, the requests it will send one after the other (more
    than any window can use).  `first_output_step`: client i's FIRST
    answer is `step * (1 + i mod (n_out // step))` tokens long, so the
    callers do not finish in lockstep and completions are spread over
    the window instead of arriving in waves of a whole batch."""
    clients = int(mix["clients"])
    per = int(mix.get("requests_per_client", 48))
    fixed = np.random.default_rng(int(mix.get("mix_seed", 0)))
    plens = draw_lengths(mix["prompt_len"], clients * per, fixed)
    olens = draw_lengths(mix["output_len"], clients * per, fixed)
    fields = draw_fields(mix, clients * per)
    rng = np.random.default_rng([int(seed), 0x5EED])
    if mix.get("seed_reorders"):
        order = rng.permutation(clients * per)
        plens, olens = plens[order], olens[order]
        fields = [fields[i] for i in order]
    step = int(mix.get("first_output_step", 0))
    prompts = _prompts(mix, plens, rng, vocab)
    out, k = [], 0
    for c in range(clients):
        mine = []
        for j in range(per):
            n_out = int(olens[k])
            if step and j == 0:
                n_out = step * (1 + c % max(1, n_out // step))
            mine.append(Request(k, 0.0, prompts[k], n_out, client=c,
                                fields=fields[k]))
            k += 1
        out.append(mine)
    return out


# ----------------------------------------------------------------------
# the client (one thread, asyncio + aiohttp)
# ----------------------------------------------------------------------
def body_of(req: Request) -> str:
    """What is sent: with no fields, byte for byte what it always was."""
    return json.dumps({"tokens": [req.prompt], "max_new_tokens": req.n_out,
                       **req.fields})


async def _post(session, url: str, req: Request, rec: Record,
                t0: float) -> None:
    rec.prompt_len, rec.want = len(req.prompt), req.n_out
    rec.fields = req.fields
    rec.sent_s = time.perf_counter() - t0
    try:
        async with session.post(url, data=body_of(req)) as r:
            rec.status = r.status
            body = await r.read()
        rec.done_s = time.perf_counter() - t0
        if rec.status == 200:
            out = json.loads(body)
            rec.got = len(out["tokens"][0])
            rec.engine_s = float(out.get("engine_s", math.nan))
            rec.replica = str(out.get("replica", ""))
            rec.ok = rec.got == req.n_out
            if not rec.ok:
                rec.error = f"asked {req.n_out} tokens, got {rec.got}"
        else:
            rec.error = body[:200].decode(errors="replace")
    except asyncio.CancelledError:
        rec.error = "not answered by the end of the drain"
        rec.cut = True
        raise
    except Exception as e:  # a failed request is a result, not a crash
        rec.done_s = time.perf_counter() - t0
        rec.error = f"{type(e).__name__}: {e}"


async def _open_loop(url: str, reqs: List[Request], seconds: float,
                     drain_s: float) -> List[Record]:
    import aiohttp

    recs = [Record(r.idx, r.due_s) for r in reqs]
    timeout = aiohttp.ClientTimeout(total=None)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as s:
        t0 = time.perf_counter()
        tasks = []
        for req, rec in zip(reqs, recs):
            wait = req.due_s - (time.perf_counter() - t0)
            if wait > 0:
                await asyncio.sleep(wait)
            tasks.append(asyncio.ensure_future(_post(s, url, req, rec, t0)))
        left = seconds + drain_s - (time.perf_counter() - t0)
        if tasks:
            _, pending = await asyncio.wait(tasks, timeout=max(left, 0.0))
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
    return recs


async def _closed_loop(url: str, plans: List[List[Request]], seconds: float,
                       drain_s: float) -> List[Record]:
    import aiohttp

    recs: List[Record] = []
    timeout = aiohttp.ClientTimeout(total=None)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as s:
        t0 = time.perf_counter()

        async def client(plan):
            for req in plan:
                now = time.perf_counter() - t0
                if now >= seconds:
                    return
                rec = Record(req.idx, now)
                recs.append(rec)
                await _post(s, url, req, rec, t0)

        # no caller sends after the window's end, and the loop returns as
        # soon as each has its last answer: the wait is what the engine
        # needs to empty, `drain_s` only its ceiling.  The requests out at
        # the window's end are waited for because the closed reading
        # credits their tokens (`produced_per_s`); one still unanswered
        # at the ceiling is cut, and a closed cell's `correct` counts it
        tasks = [asyncio.ensure_future(client(p)) for p in plans]
        _, pending = await asyncio.wait(tasks, timeout=seconds + drain_s)
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
    return recs


def run_open_loop(url, reqs, seconds, drain_s) -> List[Record]:
    return asyncio.run(_open_loop(url, reqs, seconds, drain_s))


def run_closed_loop(url, plans, seconds, drain_s) -> List[Record]:
    return asyncio.run(_closed_loop(url, plans, seconds, drain_s))


# ----------------------------------------------------------------------
# reduction of the client's records (the yardstick: kept here)
# ----------------------------------------------------------------------
def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank percentile; None on an empty sample."""
    v = sorted(values)
    if not v:
        return None
    return v[min(len(v) - 1, max(0, math.ceil(q / 100.0 * len(v)) - 1))]


# A closed loop's rate is read from this share of the window on.  By
# then the slots are full in every closed cell (the slowest to fill,
# 128 slots at 16 admissions a tick, takes 8 ticks, under 3.3 s of
# 30), and the first requests' arrival race and their one-off delay
# (all callers arrive at once, the first tick admits a part) lie before
# it.  The rate is NOT yet level there where a request lives long: the
# callers' short first answers make the early ticks heavy with
# admissions, and `dots3_docqa_closed_16k` credits 2,860 tokens/s over
# 6-10 s, 3,210 over 10-14 s and 3,310-3,370 from there on, one
# request's life in (chip, PR 49).  That climb is the traffic's, the
# same in every run: read from S/2 on, six runs spread 0.56% for 0.61%,
# no steadier for fewer seconds.  One constant for every cell: no field
# of a traffic file.
CLOSED_READ_FROM = 0.2


def _share_by(r: Record, t: float) -> float:
    """The share of an answered request credited by the instant `t`:
    what it was asked for, spread evenly over `[sent_s, done_s]`."""
    life = r.done_s - r.sent_s
    if life <= 0.0:
        return 1.0 if t >= r.done_s else 0.0
    return min(1.0, max(0.0, (t - r.sent_s) / life))


def _produced_by(r: Record, t: float) -> float:
    """Tokens of an answered request credited by the instant `t`: its
    `got` tokens spread evenly over `[sent_s, done_s]`."""
    return r.got * _share_by(r, t)


def produced_per_s(recs: List[Record], seconds: float) -> float:
    """A closed loop's tokens per second AS THEY ARE PRODUCED, from the
    client's clock alone: every answered request (status 200, as many
    tokens as asked) is credited its tokens evenly over the time the
    caller waited for it, and the reading is the credit that falls
    between `CLOSED_READ_FROM` of the window and its end, over that
    time.  A closed loop's caller always has exactly one request out,
    so this is each caller's tokens per second of waiting, summed over
    the callers (Little's law): continuous in where the window's end
    falls, blind to a shift of the whole run.  The count of answers
    that ENDED inside the window moves in steps of one engine tick's
    completions instead: 0.36% of itself in the quickest closed cell
    and 1.98% in the slowest (PERF.md section 2).  A failed, short or
    never answered request is credited nothing.  Against the engine's
    own count of the same seconds of the same run (`row_steps_live` and
    a first token an admission, one traced run a cell) it reads within
    0.4% where the tick ring is stamped (`dots3` twice, Brumby), and
    between -2.3% and +1.6% over all five cells by the coarser
    per-second account: the start's climb and the drain's thinning
    engine are no steady state (chip, PR 49; PERF.md section 6)."""
    a = CLOSED_READ_FROM * seconds
    done = [r for r in recs if r.ok and not math.isnan(r.done_s)]
    return sum(_produced_by(r, seconds) - _produced_by(r, a)
               for r in done) / (seconds - a)


def credited(recs: List[Record], seconds: float) -> dict:
    """A closed loop's answered requests, each with what it was
    (`prompt_len`, `got`, `fields`) and the SHARE of it that
    `produced_per_s` credits between `from_s` and `to_s`: whatever else
    is counted a request (the operations it needed: `serve_mfu`) is
    credited over the same seconds as its tokens.  A failed, short or
    never answered request is not listed."""
    a = CLOSED_READ_FROM * seconds
    return {"from_s": a, "to_s": seconds, "requests": [
        {"prompt_len": r.prompt_len, "got": r.got, "fields": r.fields,
         "share": _share_by(r, seconds) - _share_by(r, a)}
        for r in recs if r.ok and not math.isnan(r.done_s)]}


def summarize(recs: List[Record], seconds: float, miss_ms: float,
              closed: bool = False) -> dict:
    """`tokens_per_s`: open loop, output tokens of requests that
    completed inside the window, over the window; closed loop, tokens
    as they are produced (`produced_per_s`).  The first reading is kept
    for every run as `tokens_ended_in_window_per_s` (a note, no metric).
    `latency_ms`: one value per request SENT, from its due instant; a
    failed, short or unanswered request counts `miss_ms` (worse than
    any answer).  `cut_at_end`: closed loop, requests still unanswered
    when the drain's ceiling was reached; they have no `done_s`, so no
    credit, and a closed cell's `correct` holds their number to 0.
    `by_field`: only where requests carried fields (`request_fields`),
    per field and value the answered requests and their tokens: a
    count, no metric.  `credited`: closed loop only, the answered
    requests one by one with their share of credit in the read window
    (`credited`)."""
    cut = [r for r in recs if r.cut and closed]
    recs = [r for r in recs if not (r.cut and closed)]
    done_in = [r for r in recs if r.ok and r.done_s <= seconds]
    lat = [(r.done_s - r.due_s) * 1e3 if r.ok else miss_ms for r in recs]
    late = [(r.sent_s - r.due_s) * 1e3 for r in recs
            if not math.isnan(r.sent_s)]
    ended = sum(r.got for r in done_in) / seconds
    by_field: dict = {}
    for r in recs:
        for name, value in r.fields.items():
            seen = by_field.setdefault(name, {}).setdefault(
                str(value), {"completed": 0, "tokens": 0})
            seen["completed"] += int(r.ok)
            seen["tokens"] += r.got if r.ok else 0
    out = {
        "cut_at_end": len(cut),
        "attempted": len(recs),
        "failed": sum(not r.ok for r in recs),
        "completed_in_window": len(done_in),
        "tokens_per_s": produced_per_s(recs, seconds) if closed else ended,
        "tokens_ended_in_window_per_s": ended,
        "latency_ms": lat,
        "late_ms": late,
        "plane_overhead_ms": [
            (r.done_s - r.sent_s - r.engine_s) * 1e3 for r in recs
            if r.ok and not math.isnan(r.engine_s)],
        "per_replica": {
            rid: sum(1 for r in recs if r.replica == rid)
            for rid in sorted({r.replica for r in recs if r.replica})},
        "unanswered_at_window_end": sum(
            1 for r in recs if math.isnan(r.done_s) or r.done_s > seconds),
    }
    if by_field:
        out["by_field"] = by_field
    if closed:
        out["credited"] = credited(recs, seconds)
    return out
