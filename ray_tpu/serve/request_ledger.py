"""Per-request latency ledger for the serve path.

A `RequestLedger` is a compact timestamp struct that rides one serve
request end to end — proxy arrival → router assignment wait → replica
queue → engine admission → prefill → first token → decode → terminal
(ok / shed / rejected / error) — and is surfaced three ways at terminal
time:

  * windowed histograms (`rt_serve_*_seconds` in the metric catalog),
    observed in the process that measured each phase and shipped on the
    existing obs-frame path to the merged `/metrics`;
  * phase-attributed trace spans on the PR-12 trace plane, with
    **tail-based capture**: the ledger buffers its span tree locally
    and commits it only at terminal time, so a request whose e2e
    latency lands in the slowest K% (`RT_SERVE_TAIL_PCT`, default 5) —
    or ANY shed/rejected/errored request — retains its spans even when
    the head-sampling roll at the root said drop;
  * cumulative SLO counter blocks (`slo.empty_counters` shape) that
    replicas piggyback on health checks for the controller's burn-rate
    tracker.

Hot-path discipline: `start_request` returns None unless metrics or
tracing is enabled, and every call site is a `led is not None` test —
a disabled ledger adds zero per-request allocations (asserted in
tests/test_serve_overload.py).  The ledger itself is `__slots__`-only
and defers ALL span-dict construction to the terminal path.

Threading note: the ambient ledger rides a contextvar (like the trace
context) so it crosses the proxy → handle → router chain without
plumbing; replica-side it is re-installed explicitly inside executor
thunks because `run_in_executor` does not propagate contextvars.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ray_tpu.metrics import metric_defs as _md
from ray_tpu.serve import slo as _slo
from ray_tpu.util import tracing as _tracing

# slowest-K% capture knobs: a terminal e2e at or above the ring's
# (100 - PCT) percentile force-retains the span tree
TAIL_PCT = float(os.environ.get("RT_SERVE_TAIL_PCT", "5") or 5)
TAIL_RING = int(os.environ.get("RT_SERVE_TAIL_RING", "512") or 512)
# below this many observations the tail threshold is undefined and
# nothing qualifies as tail (refused requests are still retained)
TAIL_MIN_SAMPLES = 16

# phase name -> cataloged histogram observed at terminal time
_PHASE_METRICS = {
    "queue_wait": "rt_serve_queue_wait_seconds",
    "prefill": "rt_serve_prefill_seconds",
}
# note key -> cataloged histogram (values measured engine-side)
_NOTE_METRICS = {
    "ttft_s": "rt_serve_ttft_seconds",
    "tpot_s": "rt_serve_tpot_seconds",
    "prefill_s": "rt_serve_prefill_seconds",
    "queue_wait_s": "rt_serve_queue_wait_seconds",
}

_ledger_var: contextvars.ContextVar = contextvars.ContextVar(
    "rt_serve_ledger", default=None
)


def enabled() -> bool:
    """Ledger structs are allocated only when some consumer exists."""
    return _md.enabled() or _tracing.is_enabled()


def current() -> Optional["RequestLedger"]:
    return _ledger_var.get()


class use_ledger:
    """Install `led` as the ambient request ledger (set + reset in the
    same frame).  None is a no-op so call sites stay branch-free."""

    def __init__(self, led: Optional["RequestLedger"]):
        self._led = led
        self._token = None

    def __enter__(self):
        if self._led is not None:
            self._token = _ledger_var.set(self._led)
        return self._led

    def __exit__(self, exc_type, exc, tb):
        if self._token is not None:
            _ledger_var.reset(self._token)
            self._token = None
        return False


class _TailSampler:
    """Bounded ring of recent completed-request e2e latencies defining
    the slowest-K% retention threshold for this process."""

    __slots__ = ("_ring", "_lock")

    def __init__(self, maxlen: int = TAIL_RING):
        self._ring: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()

    def observe(self, e2e_s: float):
        with self._lock:
            self._ring.append(e2e_s)

    def is_tail(self, e2e_s: float) -> bool:
        with self._lock:
            n = len(self._ring)
            if n < TAIL_MIN_SAMPLES:
                return False
            k = max(1, int(n * TAIL_PCT / 100.0))
            threshold = sorted(self._ring)[-k]
        return e2e_s >= threshold

    def reset(self):
        with self._lock:
            self._ring.clear()


_tail = _TailSampler()


# per-process cumulative SLO counter blocks, keyed (app, deployment);
# replicas ship their process's block on the health piggyback
_slo_lock = threading.Lock()
_slo_agg: Dict[tuple, Dict[str, Any]] = {}


def slo_snapshot() -> Dict[str, Dict[str, Any]]:
    """{"app/deployment": counter block} for this process (cumulative;
    the controller folds deltas)."""
    with _slo_lock:
        return {
            f"{app}/{dep}": {
                "n": blk["n"], "errors": blk["errors"],
                "ttft": list(blk["ttft"]), "e2e": list(blk["e2e"]),
            }
            for (app, dep), blk in _slo_agg.items()
        }


def _slo_record(app: str, dep: str, e2e_s: float,
                ttft_s: Optional[float], ok: bool):
    with _slo_lock:
        blk = _slo_agg.get((app, dep))
        if blk is None:
            blk = _slo_agg[(app, dep)] = _slo.empty_counters()
        blk["n"] += 1
        if not ok:
            blk["errors"] += 1
        blk["e2e"][_slo.bucket_index(e2e_s)] += 1
        if ttft_s is not None:
            blk["ttft"][_slo.bucket_index(ttft_s)] += 1


def _reset_for_tests():
    _tail.reset()
    with _slo_lock:
        _slo_agg.clear()


class RequestLedger:
    """One request's phase timeline.  Built by `start_request`, carried
    ambiently (`use_ledger`) or explicitly, closed exactly once by
    `finish`."""

    __slots__ = ("kind", "app", "deployment", "replica", "trace_id",
                 "root_id", "parent_id", "sampled", "t0", "t_end",
                 "phases", "notes", "status", "reason", "_cur", "_cur_t",
                 "_extra_spans")

    def __init__(self, kind: str, app: str, deployment: str,
                 replica: str = "-"):
        self.kind = kind
        self.app = app
        self.deployment = deployment
        self.replica = replica
        self.t0 = time.time()
        self.t_end: Optional[float] = None
        self.phases: List[tuple] = []  # (name, t_start, t_end)
        self.notes: Dict[str, Any] = {}
        self.status = "ok"
        self.reason: Optional[str] = None
        self._cur: Optional[str] = None
        self._cur_t = self.t0
        self._extra_spans: List[Dict[str, Any]] = []
        # trace identity: join an ambient sampled trace, inherit a
        # NOT_SAMPLED decision (fresh id kept aside for tail capture),
        # or make the head-sampling roll ourselves as a new root
        self.parent_id: Optional[str] = None
        if _tracing.is_enabled():
            parent = _tracing.current_context()
            if parent and parent.get("trace_id"):
                self.trace_id = parent["trace_id"]
                self.parent_id = parent.get("span_id")
                self.sampled = True
            else:
                self.trace_id = _tracing.new_id()
                self.sampled = (parent is None and _tracing._sampled())
            self.root_id = _tracing.new_id()
        else:
            self.trace_id = ""
            self.root_id = ""
            self.sampled = False

    # -- trace context ------------------------------------------------
    def ctx(self) -> Optional[Dict[str, str]]:
        """Ambient trace context to install around downstream work.
        Sampled requests expose the real (trace_id, root span) so the
        runtime's submit/run spans join the request's trace; unsampled
        ones expose NOT_SAMPLED so the whole lineage does zero span
        work — tail capture then retains the ledger's own phase tree."""
        if not self.trace_id:
            return None
        if self.sampled:
            return {"trace_id": self.trace_id, "span_id": self.root_id}
        return dict(_tracing.NOT_SAMPLED)

    # -- phase timeline -----------------------------------------------
    def begin(self, phase: str, now: Optional[float] = None):
        """Close the current phase (if any) and open `phase`.  Phases
        are contiguous, so their durations sum to e2e exactly."""
        now = time.time() if now is None else now
        if self._cur is not None:
            self.phases.append((self._cur, self._cur_t, now))
        self._cur = phase
        self._cur_t = now

    def note(self, key: str, value: Any):
        self.notes[key] = value

    def add_span(self, name: str, start: float, end: float,
                 **attrs: Any):
        """Attach a pre-measured child span (engine-side phases carry
        exact loop-thread timestamps).  Buffered until terminal time —
        tail capture decides whether it ever records."""
        if not self.trace_id:
            return
        rec: Dict[str, Any] = {
            "name": name, "trace_id": self.trace_id,
            "span_id": _tracing.new_id(), "parent_id": self.root_id,
            "start": start, "end": end, "kind": "INTERNAL",
        }
        if attrs:
            rec["attrs"] = attrs
        self._extra_spans.append(rec)

    # -- terminal -----------------------------------------------------
    def finish(self, status: str = "ok", reason: Optional[str] = None,
               now: Optional[float] = None) -> float:
        """Close the ledger exactly once: observe histograms, fold SLO
        counters, and commit the span tree when retained (sampled, or
        refused/errored, or slowest-K% e2e).  Returns e2e seconds."""
        if self.t_end is not None:
            return self.t_end - self.t0
        now = time.time() if now is None else now
        if self._cur is not None:
            self.phases.append((self._cur, self._cur_t, now))
            self._cur = None
        if status != "ok":
            # zero-duration terminal marker: refused/errored requests
            # carry their reason as an inspectable phase (and span)
            self.phases.append((f"terminal:{status}", now, now))
        self.t_end = now
        self.status = status
        self.reason = reason
        e2e = now - self.t0
        tags = {"app": self.app, "deployment": self.deployment,
                "replica": self.replica}
        _md.observe("rt_serve_e2e_seconds", e2e, tags=tags)
        for name, ts, te in self.phases:
            mname = _PHASE_METRICS.get(name)
            if mname is not None:
                _md.observe(mname, te - ts, tags=tags)
        for key, mname in _NOTE_METRICS.items():
            v = self.notes.get(key)
            if v is not None:
                _md.observe(mname, float(v), tags=tags)
        # SLO counters fold replica-side only: the proxy-side ledger
        # would double-count the same request
        if self.replica != "-":
            ttft = self.notes.get("ttft_s")
            _slo_record(self.app, self.deployment, e2e,
                        float(ttft) if ttft is not None else None,
                        ok=(status == "ok"))
        # -- tail-based span retention --------------------------------
        if self.trace_id and _tracing.is_enabled():
            refused = status != "ok"
            retain = self.sampled or refused or _tail.is_tail(e2e)
            if not refused:
                _tail.observe(e2e)
            if retain:
                _tracing.record_spans(self._spans())
        self._extra_spans = []
        return e2e

    def _spans(self) -> List[Dict[str, Any]]:
        attrs: Dict[str, Any] = {
            "status": self.status, "kind": self.kind, "app": self.app,
            "deployment": self.deployment, "replica": self.replica,
        }
        if self.reason:
            attrs["reason"] = self.reason
        for k, v in self.notes.items():
            attrs[k] = v
        root: Dict[str, Any] = {
            "name": f"serve.request:{self.deployment}",
            "trace_id": self.trace_id, "span_id": self.root_id,
            "parent_id": self.parent_id, "start": self.t0,
            "end": self.t_end, "kind": "SERVER", "attrs": attrs,
        }
        if self.status != "ok":
            root["error"] = self.reason or self.status
        out = [root]
        for name, ts, te in self.phases:
            out.append({
                "name": f"serve.{name}", "trace_id": self.trace_id,
                "span_id": _tracing.new_id(), "parent_id": self.root_id,
                "start": ts, "end": te, "kind": "INTERNAL",
            })
        out.extend(self._extra_spans)
        return out


def start_request(kind: str, app: str, deployment: str,
                  replica: str = "-") -> Optional[RequestLedger]:
    """The single ledger entry point: None (and therefore zero further
    allocations) unless metrics or tracing is on."""
    if not enabled():
        return None
    return RequestLedger(kind, app, deployment, replica)


class EngineTicket:
    """The engine-side sliver of the ledger: one per admitted request.
    The engine stamps every request once per boundary for its own
    `request_ring` and hands the ticket those SAME wall-clock stamps
    (plain attribute stores, no clock read of the ticket's own), which
    are assembled into ledger notes + spans only at the request's
    terminal tick."""

    __slots__ = ("ledger", "trace_ctx", "t_submit", "t_admit",
                 "t_prefill_done", "t_first", "t_done", "n_tokens")

    def __init__(self, ledger: Optional[RequestLedger],
                 trace_ctx: Optional[Dict[str, str]],
                 t_submit: Optional[float] = None):
        self.ledger = ledger
        self.trace_ctx = trace_ctx
        self.t_submit = time.time() if t_submit is None else t_submit
        self.t_admit = 0.0
        self.t_prefill_done = 0.0
        self.t_first = 0.0
        self.t_done = 0.0
        self.n_tokens = 0

    def admitted(self, now: float):
        self.t_admit = now

    def prefilled(self, now: float):
        self.t_prefill_done = now

    def first_token(self, now: float):
        self.t_first = now

    def done(self, n_tokens: int, now: Optional[float] = None):
        """Terminal assembly: compute TTFT/TPOT/prefill, note them on
        the ledger (the replica's `finish` observes the histograms with
        the right tags) and attach the engine phase spans."""
        self.t_done = time.time() if now is None else now
        self.n_tokens = n_tokens
        led = self.ledger
        ttft = (self.t_first - self.t_submit) if self.t_first else None
        prefill = ((self.t_prefill_done - self.t_admit)
                   if self.t_prefill_done and self.t_admit else None)
        tpot = None
        if self.t_first and n_tokens > 1:
            tpot = (self.t_done - self.t_first) / (n_tokens - 1)
        if led is not None:
            if ttft is not None:
                led.note("ttft_s", ttft)
            if prefill is not None:
                led.note("prefill_s", prefill)
            if tpot is not None:
                led.note("tpot_s", tpot)
            led.note("n_tokens", n_tokens)
            if self.t_admit:
                led.add_span("serve.admission", self.t_submit,
                             self.t_admit)
            if prefill is not None:
                led.add_span("serve.prefill", self.t_admit,
                             self.t_prefill_done)
            if self.t_first:
                led.add_span("serve.decode", self.t_prefill_done
                             or self.t_first, self.t_done,
                             n_tokens=n_tokens)
        elif self.trace_ctx and self.trace_ctx.get("trace_id"):
            # direct engine use under a sampled trace (no serve ledger):
            # record the phase spans immediately
            spans = []
            if self.t_admit:
                spans.append(self._span("serve.admission",
                                        self.t_submit, self.t_admit))
            if prefill is not None:
                spans.append(self._span("serve.prefill", self.t_admit,
                                        self.t_prefill_done))
            if self.t_first:
                spans.append(self._span(
                    "serve.decode", self.t_prefill_done or self.t_first,
                    self.t_done))
            _tracing.record_spans(spans)

    def refused(self, reason: str, now: Optional[float] = None):
        """Shed/rejected inside the engine: stamp the terminal reason
        on the ledger (the replica-side finish records the terminal
        phase; tail capture always retains refused requests)."""
        self.t_done = time.time() if now is None else now
        led = self.ledger
        if led is not None:
            led.note("engine_refused", reason)
            led.add_span("serve.shed", self.t_submit, self.t_done,
                         reason=reason)

    def _span(self, name: str, start: float, end: float) -> Dict[str, Any]:
        return {
            "name": name, "trace_id": self.trace_ctx["trace_id"],
            "span_id": _tracing.new_id(),
            "parent_id": self.trace_ctx.get("span_id"),
            "start": start, "end": end, "kind": "INTERNAL",
        }


def engine_ticket(t_submit: Optional[float] = None
                  ) -> Optional[EngineTicket]:
    """Ticket for one engine submit (`t_submit`: the engine's own
    submit stamp): rides the ambient ledger and/or a sampled ambient
    trace; None (no allocation) when neither exists."""
    led = _ledger_var.get()
    ctx = _tracing.current_context() if _tracing.is_enabled() else None
    if led is None and (ctx is None or not ctx.get("trace_id")):
        return None
    return EngineTicket(led, ctx, t_submit)
