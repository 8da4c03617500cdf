"""Continuous-batching LLM engine: step-level scheduling over a PAGED
KV cache with radix prefix reuse.

Reference capability: the vLLM-on-Ray serving pattern (continuous
batching) extended with its two production levers — PagedAttention
(Kwon et al., SOSP 2023: block-granular KV allocation) and
RadixAttention (Zheng et al., 2024: prefix-tree KV sharing) — rebuilt
TPU-native.  New requests join a RESIDENT decode batch mid-flight; the
KV cache is one fixed block pool instead of a per-slot `max_len` ring.

TPU-native design points:
- STATIC shapes from a SMALL family of compiled programs: the block
  pool `[L, num_blocks, block_size, KV, hd]` is allocated once; each
  chunk dispatch gathers every slot's live blocks into a dense
  `[L, slots, W*block_size, ...]` view, runs `chunk` decode steps on
  it (one `lax.scan` per dispatch, per-row positions via
  `llama.decode_step_rows`), and scatters the blocks back.  The gather
  width W is the pow-2 bucket of the LONGEST live sequence's block
  count — per-step attention cost tracks LIVE tokens, not the pool
  budget, killing the measured "ring size is a per-step tax" cost
  (PERF.md round 5: a 1024-ring ran ~20x slower than a 192-ring).
- FUSED DECODE KERNEL (`decode_kernel="pallas"`, what "auto" means
  on TPU): the gather/scatter copies die entirely —
  the same `llama.decode_step_rows`, handed the block tables, reads and
  writes the pool IN PLACE through the Pallas kernels in
  `ops/paged_attention.py`
  (tables in SMEM, split-KV walk with an online softmax,
  `input_output_aliases` for the append).  The route is resolved ONCE
  and never downgraded: the kernels compile and run a warm-up chunk in
  `__init__`, or the engine fails to start.  The gather route above
  remains the reference (and what "auto" means off-TPU); both produce
  the same greedy tokens (`tests/test_paged_attention.py`).  With
  `kv_dtype="int8"` the pool stores per-row-scaled int8 K/V (half the
  HBM — double the resident batch at a fixed budget) and the kernel
  fuses the dequant; the gather route dequants the gathered view
  and requantizes ONLY the rows each chunk wrote, so stored KV never
  drifts through repeated round trips.
- RADIX PREFIX CACHE: prompt prefixes are cached in a block-granular
  token trie (`serve/kv_cache.py`).  A request whose prompt prefix is
  cached pins those blocks (zero-copy sharing — its block table simply
  points at them) and prefills only the suffix, attending over the
  gathered prefix KV (`llama.forward_with_prefix`).  Completed
  requests donate their full prompt blocks to the trie; unpinned
  nodes are LRU-evicted when the pool runs low.  The dominant
  consumer-scale shape — a shared system prompt — skips its prefill
  entirely after the first request.
- CHUNKED stepping + ONE host transfer per chunk, exactly as before:
  the chunk emits its pre-chunk token row so admission never needs a
  device->host read, and the token read of chunk N overlaps chunk
  N+1's compute.
- ONE PACKED PREFILL A TICK: a tick's cache-miss admissions lie end
  to end in one row of `N` tokens and are prefilled by ONE program
  (`prefill_packed_n<N>`: a segment mask, the head on each prompt's
  last row only, the greedy pick and the block writes inside it), so
  they share one read of the weights and nothing comes back to the
  host.  `N` comes from a small closed set (`_pack_sizes`), all of it
  compiled and run once at start on the kernel route.  A model whose
  prefill reads the cache through the block table packs its prefix
  HITS the same way (`suffix_prefill_packed_n<N>`).
- A ROW THAT OWES NO TOKEN COSTS NOTHING IT CAN AVOID: the device holds
  `stop[slots]` beside `pos` and `tok`, set at admission to `T + n_new
  - 1`; in every decode step a row is live iff `pos < stop`.  A dead
  row (a slot never used, a budget that ended inside the chunk, a
  finished row whose harvest lags) attends nothing, writes nothing
  into the cache and stays where it is (`serve/engine_model.py`); the
  host never tells the device that a row ended.  `tick_ring` counts
  both kinds of row-step (`row_steps_live`, `row_steps`).
- A FINISHED ROW'S SLOT DOES NOT WAIT FOR ITS HARVEST: budgets are
  deterministic, so the dispatch that moves a row's mirror `pos_host`
  to its `stop` is the dispatch of its last wanted step, and the slot,
  the blocks and the trie path go back right there (`_hand_off`) for
  the next tick's admission; the request drains in `_handed` until
  that chunk's harvest resolves it.  Only where the DEVICE counts what
  a chunk produced (`device_counts`) is a row released at its harvest.

Greedy outputs are bit-identical to a dedicated `llama.generate` for
the same prompt, with the prefix cache on or off
(`tests/test_llm_engine.py`).
"""

from __future__ import annotations

import logging
import math
import os
import threading
import time as _time
from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from ray_tpu.exceptions import BackPressureError, DeadlineExceededError
from ray_tpu.serve import request_ledger as _rl
from ray_tpu.serve.kv_cache import SCRATCH_BLOCK, BlockPool, RadixCache

logger = logging.getLogger(__name__)


# finished requests kept in stats()["request_ring"]: bounded so that a
# pickled stats() stays small on the 2 s health-check path (64 KiB with
# every ring and account at its widest: 90 bytes a record here.  512
# until the launches' stamp took 7 KB of that room)
REQUEST_RING = 448


# requests a tick admits at most: the cap keeps one tick's admission
# work from starving the active slots of decode chunks.  Also the rows
# one packed prefill program sets (its `K`)
ADMIT_BUDGET = 16

# the smallest packed prefill: at and under this many tokens a prefill
# costs the read of the weights, so a smaller program would be no faster
PACK_MIN_TOKENS = 128

# a tick that waited less than this for the previous chunk's tokens
# found the chunk FINISHED: the device may have run dry (`starved`)
STARVED_WAIT_S = 1e-3

# a launch (the host's call that hands a program to the device) that
# took longer than this did not return when the program was queued: the
# device's queue was full and the host BLOCKED inside the call until a
# program ahead of it ended (`launch_blocked_s`).  That is the device's
# time, not the host's.  Set from the chip: over 3 x the slowest call of
# a STARVED tick, whose queue is short by construction (2.19 ms: a
# prefill's call, which carries its host-made arrays to the device, takes
# 1.2-4.9 ms unblocked and a chunk's 0.3-1.5, whatever the program;
# docs/observability.md has the distribution by program family)
LAUNCH_BLOCKED_S = 8e-3

# a tick is a STALL, kept whole with its neighbours in stats()["stalls"]
# (the last STALLS_KEPT), when it compiled nothing and its wall is over
# both of these: a floor, and a multiple of the tick EMA before it
STALL_MIN_S = 1.0
STALL_FACTOR = 4.0
STALLS_KEPT = 4

# the phases of a tick, where the work happens: each is an
# `engine.<name>` span of a profiler session AND `<name>_s` in the
# tick's record, from one stamp (`_Phase`)
PHASES = ("wait", "plan", "prefill", "dispatch", "device_wait",
          "harvest_host")
# a phase's key in the tick record (one string object each: a pickle
# writes a key it has met as a reference)
_PHASE_KEYS = tuple((p, p + "_s") for p in PHASES)
# what `_Phase` sums over a tick: the phases, and the launches' stamp
_TICK_SUMS = PHASES + ("launch",)
# a starved tick's fields: the host's gap before its first program,
# and the part of it that lies before the tick (of the rest, `plan_s`
# is the tick's plan and what is left its packing up to the launch)
GAPS = ("host_gap_s", "gap_harvest_host_s")
# what a tick that prefilled adds to stats()'s cumulative counters
PREFILLED = ("prefill_calls", "prefill_rows", "prefill_tokens",
             "prefill_padded_tokens")
# a tick's launches, from the stamp in `_launch`: how many, all the time
# inside the calls, and the whole time of those that blocked.  PARTS of
# `prefill_s` / `dispatch_s`, not phases beside them
LAUNCHED = ("launches", "launch_s", "launch_blocked_s")

# stats()["tick_account"]: the tick records summed by the wall second a
# tick began in, the last ACCOUNT_SECONDS seconds that had a tick.  A
# column is a tick field summed (`ticks` counts them; `starved`,
# `stalled` count the ticks that were); a `<x>_us` column is the
# field `<x>_s` in whole microseconds, which pickle smaller than floats
ACCOUNT_SECONDS = 96
_ACCOUNT_TIMES = ("tick_s",) + tuple(k for _, k in _PHASE_KEYS) + (
    "cpu_s", "proc_cpu_s") + GAPS + ("gap_plan_s",)
_ACCOUNT_COUNTS = ("starved", "stalled", "row_steps",
                   "row_steps_live") + PREFILLED
ACCOUNT_FIELDS = ("sec", "ticks") + tuple(
    k[:-2] + "_us" for k in _ACCOUNT_TIMES) + _ACCOUNT_COUNTS
# ... and the launches' three columns AFTER them: a reader zips a row's
# columns by name, and a row of before is a prefix of a row of now.
# (A tuple of their own because the benchmark's test of the account's
# readers holds ACCOUNT_FIELDS to what it was)
ACCOUNT_LAUNCH_FIELDS = ("launches", "launch_us", "launch_blocked_us")
_ACCOUNT_KEYS = _ACCOUNT_TIMES + _ACCOUNT_COUNTS + LAUNCHED
_ACCOUNT_COLUMNS = ACCOUNT_FIELDS + ACCOUNT_LAUNCH_FIELDS
# the one column no tick carries under its name: a starved tick's plan
# is its `plan_s`
_GAP_PLAN = ACCOUNT_FIELDS.index("gap_plan_us")

# stats()["launch_account"]: the launches since the loop began, summed
# by the program's name and by whether a profiler session was recording
# (`traced` 1: the launches of the LATEST session alone).  `rows` ..
# `live_tokens` are what the programs held, as the callers of `_launch`
# say it (`padded_tokens` their `N`); `blocked` counts the launches
# over LAUNCH_BLOCKED_S.  A device trace prints a program as
# `jit_<program>(<hash>)`: its seconds there, against these counts
LAUNCH_HELD = ("rows", "tokens", "N", "attended_pairs", "rows_live",
               "live_tokens")
LAUNCH_ACCOUNT_FIELDS = (
    "program", "traced", "launches", "rows", "tokens", "padded_tokens",
    "attended_pairs", "rows_live", "live_tokens", "launch_us", "blocked")


def _cpu_now() -> tuple:
    """(this thread's, the whole process's) CPU seconds so far."""
    return _time.thread_time(), _time.process_time()


def _account_row(sums: List[float]) -> tuple:
    """A second's sums as its row: the times in whole microseconds."""
    return tuple(round(v * 1e6) if f.endswith("_us") else v
                 for f, v in zip(_ACCOUNT_COLUMNS, sums))


class _Phase:
    """One stamp, two surfaces: entering opens the `engine.<name>` span
    (a no-op outside a profiler session) and leaving adds the elapsed
    time to the current tick's `<name>_s`, so a span and a field cannot
    disagree.  `t_end` is the stamp it left at."""

    __slots__ = ("_sums", "_name", "_span", "t0", "t_end")

    def __init__(self, sums: Dict[str, float], name: str, span):
        self._sums, self._name, self._span = sums, name, span

    def __enter__(self):
        self._span.__enter__()
        self.t0 = _time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t_end = _time.perf_counter()
        self._sums[self._name] += self.t_end - self.t0
        return self._span.__exit__(*exc)


class _Launched(NamedTuple):
    """One program handed to the device, as `_launch` stamped it."""
    program: str    # the jitted function's name
    traced: bool    # a profiler session was recording
    t0: float       # the call's start (perf_counter)
    took_s: float   # ... and how long the call took to return
    blocked: bool   # over LAUNCH_BLOCKED_S, and compiled nothing
    held: Dict[str, int]  # what the program held (LAUNCH_HELD)


def attended_pairs(parts) -> int:
    """The (query, key) pairs a causal mask needs for the `(lo, hi)`
    parts of sequences one program holds: a part's `n = hi - lo`
    queries each see the `lo` keys before the part, and the part's own
    keys up to itself.  Model-free: a reader applies heads and widths."""
    return sum((hi - lo) * lo + (hi - lo) * (hi - lo + 1) // 2
               for lo, hi in parts)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _pack_sizes(top: int, block_size: int) -> List[int]:
    """The closed set of `N` a packed prefill is compiled for: powers
    of two from `PACK_MIN_TOKENS`, in whole blocks, below `top`; then
    `top` itself, a maximal sequence's blocks, which holds any prompt
    `submit` accepts.  Few on purpose: each is traced, lowered and
    loaded at every start (0.2 s a program for a dense 7B, 0.9 s for
    the latent expert model, cache warm), and past ~512 tokens a
    program is compute-bound, so a finer ladder saves only padding."""
    sizes, p = [], PACK_MIN_TOKENS
    while p < top:
        sizes.append(_cdiv(p, block_size) * block_size)
        p *= 2
    return sorted(n for n in set(sizes) if n < top) + [top]


class Generated(list):
    """The answer of a request to a model that generates by diffusion
    over blocks: the tokens, as any model's answer, with what the DEVICE
    counted for them: `decided_at` (per token, the step of its block at
    which it was decided) and `forwards` (the forwards the request was
    live in: its blocks' denoising forwards)."""

    decided_at: List[int]
    forwards: int


class _Plan(NamedTuple):
    """An admission between its two phases: the request's `_active`
    entry, and what its prefill dispatch needs."""
    req: Dict
    slot: int
    prompt: List[int]
    shared: List[int]  # the cached prefix's blocks (a hit), or none
    own: List[int]     # the blocks allocated to it, in position order


class _Part(NamedTuple):
    """Tokens `lo .. hi` of a planned request's prompt, from a block
    boundary: what ONE program prefills of it where a model packs its
    suffixes (the uncached part whole, or a chunk of it)."""
    plan: _Plan
    lo: int
    hi: int


class LlamaEngine:
    """Resident continuous-batching decode engine over a paged KV pool.

    THE MODEL SEAM.  This class is the scheduler: admission, shedding,
    slots, block tables, the radix prefix cache, the tick, its rings and
    spans, and the jit / name / donate / LRU bookkeeping of five
    program families.  What those programs compute, and what a cached
    token is, belongs to the model behind `serve/engine_model.py`: the
    engine asks it for its cache spec (`cache_leaves`: the pool leaves
    and their per-block shapes, which `BlockPool` allocates) and for the
    bodies of packed prefill, prefill, suffix prefill, KV write and the
    paged decode chunk, all with flat signatures `(params, *cache, ...)`.  The
    cache's FORMAT (`kv_dtype`) is the model's too: the engine hands
    the string over and reads it back for `stats()`.  Seven implementers,
    picked by the config's type (`engine_model_for`): `LlamaEngineModel`
    — per-head K and V pools — `LatentMoeEngineModel` — one latent
    pool, absorbed decode attention, dropless experts
    (`models/deepseek_v3.py`) — `RetentionEngineModel` — a per-slot
    state (`models/brumby.py`) — and `HybridEngineModel` — paged K and V
    beside a per-slot state in one spec (`models/lfm2.py`) — and
    `SparseLatentEngineModel` — three paged leaves of different widths,
    a learned selection, one admission family that packs a tick's
    suffixes (`models/dots3.py`) — and `WindowFullEngineModel` — paged K
    and V of two widths for the full layers beside a per-slot ring of
    window rows (`models/mimo_v2.py`) — and `BlockDiffusionEngineModel`
    — generation by diffusion over blocks (`models/sdar.py`): a step
    yields 0 or `B` tokens a row, so what a chunk produced is COUNTED ON
    THE DEVICE and the host's mirror of `pos` only bounds it
    (`_harvest`), a request carries `denoising_steps` /
    `confidence_threshold` (`submit`) and its answer is a `Generated`.
    The class keeps its name; nothing a caller passes changed.

    submit() is thread-safe and returns a `concurrent.futures.Future`
    resolving to the generated token ids (greedy — identical to what a
    dedicated `llama.generate` would produce for the same prompt).

    `max_len` caps one sequence (prompt + generation); `kv_blocks`
    sizes the SHARED pool (default: enough for every slot at max_len,
    i.e. ring-equivalent capacity — but unlike the ring, an
    over-provisioned pool costs HBM only, not per-step time).
    `prefix_cache=False` disables radix reuse (every request prefills
    its whole prompt); None, the default, is on wherever the model's
    cache can share a prefix.

    A model whose context is a per-slot STATE (`engine_model.SlotState`;
    a linear-attention model) takes the same scheduler: it holds no
    blocks, so admission is bounded by slots alone, the decode chunk
    takes no tables (one program, `decode_chunk_state`), `block_size`
    is only what a prompt is aligned to in a packed prefill, and
    `prefix_cache=True` raises `PrefixCacheUnsupportedError`.

    `prefill_chunk` (tokens, whole blocks; None: off): the most one
    prefill program holds of ONE prompt.  The packed sizes stop there,
    and a prompt whose uncached part is longer is admitted CHUNK BY
    CHUNK, each chunk a suffix prefill behind the request's own blocks
    (the cached prefix's, then those the chunks before it wrote), so a
    prompt may be as long as `max_len` allows without a program that
    attends `[max_len, max_len]`.  Beside a per-slot leaf it is allowed
    where the MODEL can carry that leaf from one chunk's program to the
    next (`engine_model.state_carries_chunks`: a ring of window rows, a
    recurrent state whose scan resumes from the slot's state; the chunk
    is then ONE program, `prefill_chunk_n<N>`, that takes and hands back
    the slot's leaves beside the blocks; `stats()["state_chunks_resumed"]`
    and the tick's field of that name count the chunks that started
    from what an earlier chunk left in the slot); a state that a
    prefill leaves once is refused.

    A model that PACKS ITS SUFFIXES (`engine_model.packs_suffixes`) has
    one admission path and one program family,
    `suffix_prefill_packed_n<N>`: all of a tick's admissions, prefix
    hits, misses and the chunks of long prompts alike, lie end to end in
    as few programs as hold them, each behind its own request's blocks
    through the block table (`_prefill_behind`), so a tick's hits share
    one read of the weights.  `N` comes from the same closed set, warmed
    at start; `prefill_chunk` caps it.  The other paged models keep one
    program a hit (`_run_suffix`).

    A model whose cache holds BOTH kinds (paged K and V in its attention
    layers, a per-slot state in the others) is admitted when a slot AND
    its blocks are free; its packed prefill is handed `blk_ids` and
    `slots`; `stats()` reports `cache_bytes_per_token` and
    `cache_bytes_per_slot` both; the prefix cache is refused as for any
    per-slot state (sharing would need the state at block boundaries)."""

    def __init__(self, cfg, params, *, slots: int = 32,
                 max_len: Optional[int] = None, chunk: int = 8,
                 block_size: int = 16, kv_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 max_queued: Optional[int] = None,
                 decode_kernel: str = "auto", kv_dtype: str = "model",
                 chunk_cache_cap: int = 8,
                 kernel_interpret: bool = False,
                 prefill_chunk: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        from ray_tpu.serve.engine_model import engine_model_for

        self._jax, self._jnp = jax, jnp
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = int(max_len or cfg.max_seq_len)
        self.chunk = chunk
        self.block_size = int(block_size)
        # blocks a maximal sequence needs (highest touched index is
        # max_len - 1)
        self._max_seq_blocks = _cdiv(self.max_len, self.block_size)
        budget = (int(kv_blocks) if kv_blocks is not None
                  else slots * self._max_seq_blocks)
        if budget < self._max_seq_blocks:
            raise ValueError(
                f"kv_blocks={budget} cannot hold one max_len sequence "
                f"({self._max_seq_blocks} blocks of {self.block_size})"
            )
        if decode_kernel not in ("auto", "pallas", "gather"):
            raise ValueError(
                f"decode_kernel={decode_kernel!r} not in "
                "('auto', 'pallas', 'gather')"
            )
        from ray_tpu.core.accelerators import device_report

        # where this engine runs: part of stats(), so a launcher learns
        # the platform from the process that owns the chip
        self._device = device_report()
        mode = decode_kernel
        if mode == "auto":
            # the fused kernel exists for TPU HBM bandwidth: auto is
            # the kernel on the chip and the compiled gather route
            # anywhere else.  Resolved once — a kernel that does not
            # compile is an error (see the warm-up below), not a reason
            # to serve through the other route
            mode = ("pallas" if self._device["platform"] == "tpu"
                    else "gather")
        self._decode_kernel = mode  # resolved: "pallas" | "gather"
        # True only when the CALLER asks (the CPU kernel tests): the
        # Pallas interpreter is a correctness vehicle, never a default
        self._kernel_interpret = bool(kernel_interpret)
        # the model's side of the seam: its cache spec, in the format
        # `kv_dtype` names (a value it does not know is refused there),
        # and the bodies of the four program families
        # (`serve/engine_model.py`)
        self._model = engine_model_for(
            cfg, kv_dtype=kv_dtype, block_size=self.block_size, chunk=chunk,
            paged=mode == "pallas", interpret=self._kernel_interpret)
        if self._model.rows_needed(1, self.max_len - 2) > self.max_len:
            raise ValueError(
                f"max_len={self.max_len} cannot hold the rows "
                f"{type(cfg).__name__} needs for its longest request "
                "(whole blocks of its block_length)")
        # the cache's KINDS: blocks through tables, one state a slot,
        # or both.  A cache with no paged leaf never asks the pool for a
        # block: admission is bounded by slots alone
        self._has_blocks = any(not leaf.per_slot
                               for leaf in self._model.cache_leaves)
        self._has_state = any(leaf.per_slot
                              for leaf in self._model.cache_leaves)
        if self._has_state or not self._model.shares_prefix:
            if prefix_cache:
                from ray_tpu.exceptions import PrefixCacheUnsupportedError

                raise PrefixCacheUnsupportedError(
                    f"prefix_cache=True: {type(cfg).__name__} " + (
                        "keeps a sequence's context as a per-slot state, "
                        "which a radix trie cannot share block by block"
                        if self._has_state else
                        "has no prefill behind a cached prefix")
                    + "; pass prefix_cache=False (or leave it unset)")
            prefix_cache = False
            if not self._has_blocks:
                budget = 1
        elif prefix_cache is None:
            prefix_cache = True
        # +1: reserved scratch block
        self._pool = BlockPool(budget + 1, spec=self._model.cache_leaves,
                               slots=slots)
        if prefix_cache and getattr(cfg, "attention", "dense") != "dense":
            # the suffix prefill (`llama.forward_with_prefix`) mirrors
            # the DENSE attention numerics; under flash/ring/ulysses
            # the full prefill would use different reduction orders and
            # a near-tie greedy argmax could diverge between cache-on
            # and cache-off — keep the bit-identity guarantee instead
            logger.info(
                "prefix cache disabled: suffix prefill matches dense "
                "attention numerics only (cfg.attention=%r)",
                cfg.attention,
            )
            prefix_cache = False
        self._radix: Optional[RadixCache] = (
            RadixCache(self.block_size, self._pool) if prefix_cache
            else None
        )

        # the pool allocates what the model's cache spec says: two
        # per-head pools (plus the int8 scale sidecars) for a Llama, one
        # latent pool for an MLA model
        self._cache = self._alloc_cache()
        # what the cache costs, fixed at allocation (stats() reports it
        # every tick): payload bytes, scale-sidecar bytes, and the bytes
        # one cached token costs by the spec (a tiling pad not counted)
        self._cache_bytes = tuple(
            sum(a.nbytes for a, leaf in zip(self._cache, self._pool.spec)
                if leaf.sidecar == side) for side in (False, True))
        self._cache_bytes_per_token = self._pool.bytes_per_token(
            self._model.n_layers)
        self._cache_bytes_per_slot = self._pool.bytes_per_slot(
            self._model.n_layers)
        self._reset_rows()

        # compiled-program families (each keyed by a static shape).
        # The chunk family is LRU-BOUNDED: each entry retains a
        # compiled executable (host + device memory) per gather width,
        # and a long-lived replica sweeping many widths would otherwise
        # grow it without bound (same rationale as _DECODE_JIT_CACHE)
        self._chunk_cache: "OrderedDict[int, object]" = OrderedDict()
        self._chunk_cache_cap = max(1, int(chunk_cache_cap))
        self._chunk_cache_evictions = 0
        self._decode_kernel_dispatches = 0   # fused-kernel chunk ticks
        self._decode_gather_dispatches = 0  # gather-route chunk ticks
        self._packed_cache: Dict[int, object] = {}         # pack size N
        self._prefill_cache: Dict[int, object] = {}        # prompt bucket
        self._suffix_cache: Dict[tuple, object] = {}       # (S_bucket, P_blocks)
        self._chunk_prefill_cache: Dict[int, object] = {}  # chunk size N
        self._write_cache: Dict[tuple, object] = {}        # (T_in, nb)

        self._lock = threading.Lock()
        # the submit queue lives under its OWN condition/lock: the
        # engine thread holds `_lock` across admission dispatches
        # (which COMPILE on new shapes — seconds), and submit() runs on
        # the replica's event loop, which must never wait that out
        # (same rationale as the bounded-wait stats())
        self._wake = threading.Condition(threading.Lock())
        self._queue: deque = deque()
        self._free: List[int] = list(range(slots))
        # slot -> dict(fut, out, want, since, pos_host, blocks, ...)
        self._active: Dict[int, Dict] = {}
        self._slot_blocks: List[List[int]] = [[] for _ in range(slots)]
        # (slot, request) of the requests HANDED OFF: their last wanted
        # step lies in a chunk already dispatched, so slot, blocks and
        # trie path went back at that dispatch (`_hand_off`); they wait
        # here, out of `_active`, for that chunk's harvest.  By request,
        # not by slot: a slot's old request may drain while its new one
        # is active, or is handed off in its turn
        self._handed: List[tuple] = []
        self._handoffs_total = 0
        self._running = True
        self._pending_toks = None  # deferred-harvest chunk (see _loop)
        # requests popped from the queue but not yet admitted: they
        # are in neither _queue nor _active while the admission loop
        # compiles/dispatches, and queue_depth must keep counting them
        # or the busiest replica under-reports exactly while it is
        # wedged in admission work (plain int: GIL-atomic updates)
        self._pending_admissions = 0
        self._chunk_seq = 0  # dispatch counter: requests are tagged
        # with the first chunk that can contain their tokens, so the
        # deferred harvest of an OLDER chunk never credits a slot's
        # new occupant with its previous occupant's tokens

        # per-tick metrics exported via stats() (live on the engine
        # thread; reads take the lock)
        self._hit_tokens = 0          # prefix tokens served from cache
        self._prefill_tokens = 0      # tokens actually prefilled
        self._prefix_hits = 0         # requests with a non-empty match
        self._prefill_calls = 0       # prefill programs (packed+suffix)
        self._prefill_rows = 0        # requests those programs prefilled
        self._prefill_padded_tokens = 0  # their sizes (N, bucket) summed
        # chunks of long prompts that started from what an earlier chunk
        # left in the slot's per-slot leaves (`_run_state_chunk`)
        self._state_chunks_resumed = self._resumed_mark = 0
        # the model's own tick counters it wants summed from the start
        self._model_sums = {k: 0 for k in self._model.summed}
        # the packed prefill's closed set of sizes and the prompts one
        # program holds: the segment mask needs the dense attention form
        top = self._max_seq_blocks * self.block_size
        self._prefill_chunk = None
        if prefill_chunk is not None:
            # a chunk prefills behind the request's own blocks; a
            # per-slot leaf has to be one the MODEL can carry from one
            # chunk's program to the next (a ring of window rows, a
            # recurrent state its scan resumes from: yes; a state that
            # a prefill leaves once: no)
            if not self._has_blocks or (
                    self._has_state
                    and not self._model.state_carries_chunks):
                raise ValueError(
                    "prefill_chunk: chunks are suffix prefills behind the "
                    "request's own blocks, which a per-slot state has not")
            if prefill_chunk < 1 or prefill_chunk % self.block_size:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be whole blocks "
                    f"of {self.block_size}")
            self._prefill_chunk = int(prefill_chunk)
            top = min(top, self._prefill_chunk)
        self._pack_sizes = _pack_sizes(top, self.block_size)
        self._pack_rows = ADMIT_BUDGET if self._model.segmented else 1
        # where a model packs its suffixes: the rows each request is
        # aligned to (whole blocks that divide every size)
        self._pack_align = self.block_size
        if self._model.packs_suffixes:
            self._pack_align = math.lcm(self.block_size, math.gcd(
                *self._pack_sizes, self._model.pack_align))
        # overload plane: bound the admission queue and shed queued
        # requests whose caller has (or must have) given up BEFORE
        # they burn prefill compute.  All counters are plain ints
        # (GIL-atomic) so submit() can reject without any engine lock.
        self.max_queued = None if max_queued is None else int(max_queued)
        if self.max_queued is not None and self.max_queued < 0:
            raise ValueError(f"max_queued={max_queued} must be >= 0")
        self._rejected_total = 0      # queue-full submit() rejections
        self._shed_expired = 0        # queued past their deadline
        self._shed_predicted = 0      # predicted TTFT > remaining budget
        self._draining = False        # begin_drain(): reject new work
        self._ttft_ema_s = 0.0
        # windowed TTFT samples (monotonic ts, ttft): the shed
        # predictor and the SLO autoscaler consume the p90 over
        # RT_SERVE_TTFT_WINDOW_S, which DECAYS as samples age out —
        # unlike the lifetime EMA (kept for back-compat reporting), a
        # storm-inflated history stops biasing decisions one window
        # after the storm ends.  Touched only on the engine thread
        # (appends in _harvest, reads in _maybe_shed/_stats_locked).
        self._ttft_window_s = float(
            os.environ.get("RT_SERVE_TTFT_WINDOW_S", "10") or 10
        )
        self._ttft_samples: deque = deque(maxlen=256)
        # tick introspection ring: the last N per-tick records (batch
        # composition, live tokens, gather width, the phases' times)
        # exposed via stats() for the dashboard and postmortems.
        # Bounded; one dict per tick, no per-request cost.
        self._tick_ring: deque = deque(maxlen=max(1, int(
            os.environ.get("RT_ENGINE_TICK_RING", "32") or 32
        )))
        self._tick_ema_s = 0.0
        # the tick being accounted: its phases' sums (`_Phase` adds;
        # `wait` is added by the loop BEFORE the tick it is carried
        # into; `launch`: all the time inside `_launch`'s calls), the
        # programs it launched, each as stamped, and how many of them
        # compiled.  Closed by `_close`
        self._phase_s: Dict[str, float] = dict.fromkeys(_TICK_SUMS, 0)
        self._launches: List[_Launched] = []
        self._launched_before: List[str] = []  # the tick before's names
        self._compiles = 0
        # (program, traced) -> its launches summed (`launch_account`),
        # and whether the last launch folded in was a traced one
        self._launch_account: Dict[tuple, List[float]] = {}
        self._traced_last = False
        # where the host's gap before the next tick starts: the return
        # of this tick's wait for the device (its end, if it had none)
        self._t_gap_from: Optional[float] = None
        # (thread, process) CPU seconds at the last close
        self._cpu_mark = (0.0, 0.0)
        # the per-second account (ACCOUNT_FIELDS): closed seconds as
        # rows of ints, and the second still open as a list of sums
        self._account: deque = deque(maxlen=ACCOUNT_SECONDS - 1)
        self._account_open: Optional[List[float]] = None
        # stalled ticks kept whole; the newest may wait for its `after`
        self._stalls: deque = deque(maxlen=STALLS_KEPT)
        # stats()'s cumulative prefill counters at the last close
        self._prefilled_mark = (0, 0, 0, 0)
        self._hit_mark = 0  # ... and its prefix-hit tokens
        self._last_gather_blocks = 0  # W of the latest chunk dispatch
        # request lifecycle ring: one record per FINISHED request (ok,
        # shed, refused or failed), the last REQUEST_RING of them, in
        # stats()["request_ring"].  Each request is stamped once, on
        # the wall clock, where the work happens (submit, admit,
        # prefill dispatched, first token harvested, done); an
        # EngineTicket, when there is one, is handed the same stamps.
        # submit() refuses on the caller's thread, so the ring and its
        # count have a small lock of their own.
        self._request_ring: deque = deque(maxlen=REQUEST_RING)
        self._finished_total = 0
        self._ring_lock = threading.Lock()
        # engine-loop spans: no-ops (a third of a microsecond each)
        # unless a jax.profiler session is active, in which case they
        # land on the host plane of the same trace as the device's ops
        self._span = jax.profiler.TraceAnnotation
        # last computed stats() dict, served when the engine lock is
        # busy (admission compiles hold it for seconds) — whole-dict
        # swaps only, so readers never see a partial snapshot.  Seeded
        # BEFORE the thread starts: the first admission's compile is
        # exactly the window the snapshot exists for, and an empty
        # dict there would blind queue-depth routing during startup
        self._stats_snapshot: Dict[str, object] = self._stats_locked()

        if self._decode_kernel == "pallas" and not self._kernel_interpret:
            self._warm_kernel_route()
        self._thread = threading.Thread(
            target=self._loop, name="llm-engine", daemon=True
        )
        self._thread.start()

    def _reset_rows(self) -> None:
        """The slots' device state, nothing admitted: `pos`, the model's
        own row beside it (`tok`: a one-token model's last token, a
        block-diffusion model's block) and `stop`.  A row is live in a
        step iff pos < stop; 0: a slot nothing was admitted to owes
        nobody a token."""
        jnp = self._jnp
        self._pos = jnp.zeros((self.slots,), jnp.int32)
        self._tok = self._model.init_tok(self.slots)
        self._stop = jnp.zeros((self.slots,), jnp.int32)

    def _alloc_cache(self) -> tuple:
        """The device arrays of the model's cache spec, zeroed."""
        return tuple(
            self._jnp.zeros(shape, dtype) for shape, dtype in
            self._pool.leaf_shapes(self._model.n_layers, self.block_size))

    def _warm_kernel_route(self) -> None:
        """Compile and run one all-idle chunk through the kernel route
        before the engine takes requests: every row's table is the
        scratch block, so only scratch is written.  On the chip this
        proves the Mosaic lowering and the in-place pool update; where
        the kernels cannot compile the engine fails HERE, at start,
        instead of serving through another route.  Then every packed
        prefill of the closed set (of a model that packs its suffixes:
        every packed suffix prefill), empty (padding only, into scratch
        or nowhere): no admission compiles after this, whatever the
        traffic."""
        tables = (self._jnp.full(
            (self.slots, 1), SCRATCH_BLOCK, self._jnp.int32),
        ) if self._has_blocks else ()
        cfn = self._chunk_step_for(int(self._has_blocks))
        self._cache = tuple(cfn(
            self.params, *self._cache, *tables, self._tok, self._pos,
            self._stop)[:len(self._cache)])
        run = (self._run_suffixes if self._model.packs_suffixes
               else self._run_packed)
        for n in self._pack_sizes:
            run(n, [])
        if self._prefill_chunk and self._model.state_carries_chunks:
            for n in self._pack_sizes:
                self._run_state_chunk(None, 0, 0, 0, 1, N=n)
        self._jax.block_until_ready(self._cache)

    # -- public surface ------------------------------------------------
    def retry_after_hint_s(self) -> float:
        """When a rejected caller should retry: the estimated time for
        the current backlog to drain one admission wave (ticks needed
        at the ≤16-per-tick admission budget, priced at the tick EMA).
        A heuristic, not a promise — floored/capped so cold engines
        (no EMA yet) and pathological backlogs still hint sanely."""
        backlog = len(self._queue) + self._pending_admissions
        per_tick = float(max(1, min(ADMIT_BUDGET, self.slots)))
        est = self._tick_ema_s * max(1.0, backlog / per_tick)
        if est <= 0.0:
            est = 1.0  # no tick has completed yet: default hint
        return max(0.05, min(30.0, est))

    def begin_drain(self) -> None:
        """Graceful scale-down entry: stop ADMITTING new requests
        (submit() rejects with BackPressureError) while live sequences
        decode to completion.  KV blocks release as each finishes;
        shutdown() then returns the pool to the allocator."""
        self._draining = True

    def submit(self, prompt_ids: List[int], max_new_tokens: int,
               timeout_s: Optional[float] = None, *,
               denoising_steps: Optional[int] = None,
               confidence_threshold: Optional[float] = None) -> Future:
        """`timeout_s` is the caller's remaining end-to-end budget: the
        request carries its admission deadline through the queue, and
        the admission loop sheds it BEFORE prefill once the deadline
        has passed (or predictably must pass) — see _maybe_shed.

        `denoising_steps`, `confidence_threshold`: a request's own
        fields of a model that generates by diffusion over blocks (None:
        the model's config's); a model that yields one token a step
        refuses them (`ValueError`, as a prompt it cannot hold).  Such a
        model's answer is a `Generated`: the tokens, with the step each
        was decided at and the forwards the request took."""
        limit = self.max_len - 1
        try:
            if not prompt_ids or len(prompt_ids) >= limit:
                raise ValueError(f"prompt length must be in [1, {limit - 1}]")
            fields = self._model.request_fields(denoising_steps,
                                                confidence_threshold)
        except ValueError as e:
            f: Future = Future()
            f.set_exception(e)
            return f
        n_new = max(1, min(int(max_new_tokens), limit - len(prompt_ids)))
        # the lifecycle's first stamp (wall clock, like the ledger's)
        t_submit = _time.time()
        # engine slice of the request's latency ledger: None (zero
        # allocations) unless an ambient ledger or sampled trace exists
        tk = _rl.engine_ticket(t_submit)
        # no pool-size check needed: __init__ guarantees the pool holds
        # a full max_len sequence, and T + n_new - 1 <= max_len - 1
        now = _time.monotonic()
        deadline = None if timeout_s is None else now + max(0.0, timeout_s)
        fut: Future = Future()

        with self._wake:
            if not self._running:
                self._refused("shutdown", t_submit, t_submit, tk,
                              len(prompt_ids))
                fut.set_exception(RuntimeError("engine is shut down"))
                return fut
            if self._draining:
                self._rejected_total += 1
                self._refused("draining", t_submit, t_submit, tk,
                              len(prompt_ids))
                fut.set_exception(BackPressureError(
                    "engine is draining (replica scaling down)",
                    retry_after_s=self.retry_after_hint_s(),
                ))
                return fut
            if (self.max_queued is not None
                    and len(self._queue) + self._pending_admissions
                    >= self.max_queued + len(self._free)):
                # bounded queue: reject NOW — queueing past the cap
                # only converts this request into a guaranteed timeout
                # that still costs a prefill.  Free slots extend the
                # bound (work that will be admitted on the next tick
                # is not really WAITING), so max_queued=0 still means
                # "serve when capacity is free, never queue" rather
                # than "reject everything".  Under saturation free
                # slots are zero and the queue is bounded at exactly
                # max_queued.
                self._rejected_total += 1
                self._refused("queue_full", t_submit, t_submit, tk,
                              len(prompt_ids))
                fut.set_exception(BackPressureError(
                    f"engine queue full (max_queued={self.max_queued})",
                    retry_after_s=self.retry_after_hint_s(),
                ))
                return fut
            if deadline is not None and now >= deadline:
                self._shed_expired += 1
                self._refused("expired_at_submit", t_submit, t_submit, tk,
                              len(prompt_ids))
                fut.set_exception(DeadlineExceededError(
                    "request budget already spent at submission",
                    timeout_s=timeout_s,
                ))
                return fut
            self._queue.append(
                (list(prompt_ids), n_new, fut, t_submit, deadline, tk, fields)
            )
            self._wake.notify()
        return fut

    def stats(self) -> Dict[str, object]:
        """Engine load/health signals (floats plus the `decode_kernel`
        / `kv_dtype` mode strings): consumed by the serve replica's
        metrics piggyback (queue-depth routing + the dashboard's
        /api/serve) and by the tick-trace benchmark.

        NON-BLOCKING by contract: the engine thread holds its lock
        across admission dispatches, which COMPILE on first use of a
        new shape (seconds to tens of seconds on a real model).  A
        health check blocked that long would get a healthy replica
        killed (health_check_timeout_s defaults to 10 s), so when the
        lock isn't free within a bounded wait this returns the last
        per-tick snapshot instead."""
        if not self._lock.acquire(timeout=0.25):
            return dict(self._stats_snapshot)
        try:
            # snapshot updated under the lock: an unlocked write here
            # could land AFTER the engine loop's fresher per-tick one
            snap = self._stats_snapshot = self._stats_locked()
        finally:
            self._lock.release()
        return dict(snap)

    def _ttft_p90(self) -> float:
        """p90 TTFT over the trailing window — 0.0 once every sample
        has aged out, so load-shedding and autoscaling decisions built
        on it decay naturally after a storm (the lifetime EMA never
        did; see _maybe_shed)."""
        cutoff = _time.monotonic() - self._ttft_window_s
        live = sorted(v for ts, v in self._ttft_samples if ts >= cutoff)
        if not live:
            return 0.0
        return live[min(len(live) - 1, int(len(live) * 0.9))]

    def _stats_locked(self) -> Dict[str, object]:
        served = self._hit_tokens + self._prefill_tokens
        cached = self._radix.cached_blocks if self._radix else 0
        with self._ring_lock:
            ring = list(self._request_ring)
            finished = self._finished_total
        unresolved = self._unresolved()
        return {
                # (a request handed off is active until its harvest, as
                # it was before slots went back at dispatch: it may be
                # counted beside the slot's next holder)
                "active": len(unresolved),
                "queued": len(self._queue),
                "free_slots": len(self._free),
                "queue_depth": (len(unresolved) + len(self._queue)
                                + self._pending_admissions),
                "live_tokens": sum(r["pos_host"] for r in unresolved),
                "blocks_total": self._pool.capacity,
                "blocks_free": self._pool.free_blocks,
                "blocks_cached": cached,
                "block_occupancy": (
                    1.0 - self._pool.free_blocks / self._pool.capacity
                ),
                "prefix_hits": self._prefix_hits,
                "prefix_hit_tokens": self._hit_tokens,
                "prefill_tokens": self._prefill_tokens,
                "prefix_hit_rate": (
                    self._hit_tokens / served if served else 0.0
                ),
                # programs dispatched, the requests they prefilled, and
                # the tokens they were compiled for (real: prefill_tokens)
                "prefill_calls": self._prefill_calls,
                "prefill_rows": self._prefill_rows,
                "prefill_padded_tokens": self._prefill_padded_tokens,
                "state_chunks_resumed": self._state_chunks_resumed,
                **self._model_sums,
                "gather_blocks": self._last_gather_blocks,
                # decode-kernel / quantization plane: which route the
                # chunk dispatches take and what the pool costs in HBM
                # (payload and int8 scale sidecar reported separately,
                # so the ½-bytes-at-equal-blocks claim stays auditable)
                "decode_kernel": self._decode_kernel,
                "kernel_interpret": self._kernel_interpret,
                "device": dict(self._device),
                "kv_dtype": self._model.kv_dtype,
                "kv_pool_bytes": self._cache_bytes[0],
                "kv_scale_bytes": self._cache_bytes[1],
                "cache_bytes_per_token": self._cache_bytes_per_token,
                # the per-slot leaves' bytes a sequence (0: none; a
                # cache of both kinds reports both above zero)
                "cache_bytes_per_slot": self._cache_bytes_per_slot,
                "decode_kernel_dispatch_total":
                    self._decode_kernel_dispatches,
                "decode_gather_dispatch_total":
                    self._decode_gather_dispatches,
                "chunk_cache_size": len(self._chunk_cache),
                "chunk_cache_evictions": self._chunk_cache_evictions,
                "ttft_ema_s": self._ttft_ema_s,
                # windowed TTFT percentile (decays to 0 as samples age
                # out): the shed predictor and AutoscalingPolicy
                # .pressure() consume THIS, not the lifetime EMA
                "ttft_p90_s": self._ttft_p90(),
                "ttft_window_s": self._ttft_window_s,
                "tick_ema_s": self._tick_ema_s,
                "ticks": self._chunk_seq,
                # tick introspection ring: last N per-tick records for
                # the dashboard / postmortems (list of small dicts;
                # numeric-bridge consumers skip non-float values)
                "tick_ring": list(self._tick_ring),
                # the stalled ticks kept whole with their neighbours.
                # (Order matters to the pickle's size: a key string
                # first met after the 512 request records is referred
                # to by 5 bytes, not 2, every time after)
                "stalls": list(self._stalls),
                # request lifecycle ring: one record per finished
                # request (see _record); `finished_total` is the last
                # record's `seq`, so a reader knows what the ring lost
                "request_ring": ring,
                "finished_total": finished,
                # of the finished, those whose slot went back at the
                # dispatch of their last chunk and not at its harvest
                "handoffs_total": self._handoffs_total,
                # overload plane (admission control + shedding):
                # consumed by the SLO autoscaler and /api/serve
                "max_queued": (-1 if self.max_queued is None
                               else self.max_queued),
                "rejected_total": self._rejected_total,
                "shed_expired": self._shed_expired,
                "shed_predicted": self._shed_predicted,
                "shed_total": self._shed_expired + self._shed_predicted,
                "draining": 1.0 if self._draining else 0.0,
                # the tick records summed by wall second (columnar: the
                # last ACCOUNT_SECONDS seconds that had a tick)
                "tick_account": {"fields": _ACCOUNT_COLUMNS,
                                 "rows": self._account_rows()},
                # the launches summed by program and by whether a
                # profiler session recorded them (columnar, ints)
                "launch_account": {"fields": LAUNCH_ACCOUNT_FIELDS,
                                   "rows": self._launch_account_rows()},
            }

    def shutdown(self):
        with self._wake:
            self._running = False
            self._wake.notify()
        self._thread.join(timeout=10)
        with self._lock:
            self._cancel_unresolved()
        with self._wake:
            for item in self._queue:
                if not item[2].done():
                    item[2].cancel()
            self._queue.clear()

    def _unresolved(self) -> List[Dict]:
        """The admitted requests whose future is open: those that hold a
        slot, and those handed off that wait for their last harvest."""
        return [*self._active.values(), *(req for _, req in self._handed)]

    def _cancel_unresolved(self) -> None:
        for req in self._unresolved():
            if not req["fut"].done():
                req["fut"].cancel()
        self._active.clear()
        self._handed.clear()

    # -- compiled-program families ------------------------------------
    def _chunk_step_for(self, W: int):
        """Chunk stepper for gather width W, under the `decode_kernel`
        knob (the bodies live behind the seam, `serve/engine_model.py`):

        - "pallas": the fused paged route — the model's decode step
          reads/writes the pool IN PLACE through the block tables (the
          Pallas kernels in `ops/paged_attention.py`); no gather, no
          scatter, no dense copy.  Per-step HBM traffic is the live KV
          once, not three times.
        - "gather": the reference route — gather every slot's blocks
          into a dense W-block view, run the model's dense-cache decode
          step, scatter the blocks back.  Per-step cost is
          O(W * block_size) per slot — live tokens, not pool budget.

        Entries are LRU-bounded at `chunk_cache_cap` programs; an
        evicted width recompiles on next use (degradation, not
        growth)."""
        fn = self._chunk_cache.get(W)
        if fn is not None:
            self._chunk_cache.move_to_end(W)
            return fn
        _fn = self._model.decode_chunk(W)
        # the name the device trace prints the program under
        # (`jit_decode_chunk_w<W>`; a per-slot cache has no width:
        # `jit_decode_chunk_state`): readers match it by prefix
        _fn.__name__ = f"decode_chunk_w{W}" if W else "decode_chunk_state"
        fn = self._jax.jit(
            _fn, donate_argnums=tuple(range(1, 1 + len(self._cache))))
        while len(self._chunk_cache) >= self._chunk_cache_cap:
            old_w, _old = self._chunk_cache.popitem(last=False)
            self._chunk_cache_evictions += 1
            logger.info(
                "chunk-program cache evicted W=%d (cap=%d, evictions=%d)",
                old_w, self._chunk_cache_cap, self._chunk_cache_evictions,
            )
        self._chunk_cache[W] = fn
        return fn

    def _prefill_packed_for(self, N: int):
        """Admission's program: up to `_pack_rows` prompts in one row
        of `N` tokens (`engine_model`'s `prefill_packed`; of a model
        that packs its suffixes, its `suffix_prefill_packed`)."""
        fn = self._packed_cache.get(N)
        if fn is None:
            if self._model.packs_suffixes:
                _pf = self._model.suffix_prefill_packed(N)
                # found with the suffix programs by their prefix
                _pf.__name__ = f"suffix_prefill_packed_n{N}"
            else:
                _pf = self._model.prefill_packed(N)
                _pf.__name__ = f"prefill_packed_n{N}"
            fn = self._packed_cache[N] = self._jax.jit(
                _pf, donate_argnums=tuple(range(1, 1 + len(self._cache))))
        return fn

    def _prefill_for(self, bucket: int):
        """One right-padded prompt -> (logits, *kv), nothing written:
        not on admission's path (a caller that wants the logits)."""
        fn = self._prefill_cache.get(bucket)
        if fn is None:
            _pf = self._model.prefill(bucket)
            _pf.__name__ = f"prefill_b{bucket}"
            fn = self._prefill_cache[bucket] = self._jax.jit(_pf)
        return fn

    def _suffix_prefill_for(self, s_bucket: int, p_blocks: int):
        """Prefix-hit prefill: gather the matched prefix blocks and run
        the suffix forward against them (compiles per (suffix-bucket,
        prefix-width) pair)."""
        key = (s_bucket, p_blocks)
        fn = self._suffix_cache.get(key)
        if fn is None:
            _pf = self._model.suffix_prefill(s_bucket, p_blocks)
            _pf.__name__ = f"suffix_prefill_s{s_bucket}_p{p_blocks}"
            fn = self._suffix_cache[key] = self._jax.jit(_pf)
        return fn

    def _chunk_prefill_for(self, N: int):
        """A chunk of ONE long prompt where the model carries a
        per-slot leaf from chunk to chunk (`engine_model`'s
        `chunk_prefill`): `N` tokens behind the request's own blocks,
        the slot's leaves taken and handed back beside them."""
        fn = self._chunk_prefill_cache.get(N)
        if fn is None:
            _pf = self._model.chunk_prefill(N)
            _pf.__name__ = f"prefill_chunk_n{N}"
            fn = self._chunk_prefill_cache[N] = self._jax.jit(
                _pf, donate_argnums=tuple(range(1, 1 + len(self._cache))))
        return fn

    def _write_blocks_for(self, t_in: int, nb: int):
        """Write freshly prefilled KV (time axis `t_in`) into `nb` pool
        blocks and set the slot's pos/tok rows.  Serves both prefill
        shapes — full prompt from position 0, or a suffix starting at a
        block boundary — since the write target is just a block-id
        list."""
        key = (t_in, nb)
        fn = self._write_cache.get(key)
        if fn is None:
            _fn = self._model.kv_write(t_in, nb)
            _fn.__name__ = f"kv_write_t{t_in}_n{nb}"
            fn = self._write_cache[key] = self._jax.jit(
                _fn, donate_argnums=tuple(range(len(self._cache))))
        return fn

    # -- request lifecycle ring ------------------------------------------
    def _record(self, status: str, t_submit: float, t_done: float,
                tokens_in: int, req: Optional[Dict] = None) -> None:
        """One record per finished request, from the stamps taken where
        the work happened (`req`: its `_active` entry, None when it
        never was admitted).  A phase the request never reached is
        None; a request refused or shed before admission spent
        `queue_s` waiting for that verdict.  `first_token_s + decode_s`
        is `t_done - t_submit` exactly."""
        rec = {
            "seq": 0, "t_done": t_done, "status": status,
            "queue_s": t_done - t_submit, "prefill_dispatch_s": None,
            "first_token_s": None, "decode_s": None, "harvests": 0,
            "tokens_in": tokens_in, "tokens_hit": 0, "tokens_out": 0,
            "prefill_rows": None, "prefill_chunks": None,
        }
        if req is not None:
            rec["queue_s"] = req["t_admit"] - t_submit
            rec["prefill_dispatch_s"] = req["t_prefill"] - req["t_admit"]
            if req["t_first"] is not None:
                rec["first_token_s"] = req["t_first"] - t_submit
                rec["decode_s"] = t_done - req["t_first"]
            rec["harvests"] = req["harvests"]
            rec["tokens_hit"] = req["tokens_hit"]
            rec["prefill_rows"] = req["prefill_rows"]
            rec["prefill_chunks"] = req["prefill_chunks"]
            rec["tokens_out"] = min(len(req["out"]), req["want"])
            if req["fields"]:  # a block-diffusion request's own
                rec["denoising_steps"] = req["fields"]["denoising_steps"]
                rec["forwards"] = req["forwards"]
        with self._ring_lock:
            self._finished_total += 1
            rec["seq"] = self._finished_total
            self._request_ring.append(rec)

    def _refused(self, reason: str, t_submit: float, now: float, tk,
                 tokens_in: int) -> None:
        """A request that ends before admission (refused by submit() or
        shed from the queue): its record, and its ticket's terminal."""
        self._record(reason, t_submit, now, tokens_in)
        if tk is not None:
            tk.refused(reason, now)

    # -- admission -----------------------------------------------------
    def _maybe_shed(self, fut: Future, deadline: Optional[float],
                    t_submit: float, tokens_in: int, tk=None) -> bool:
        """Deadline-aware load shedding, applied when a request is
        popped for admission — the last instant before it costs a
        prefill dispatch.  Sheds when the deadline has already passed,
        or when the predicted time-to-first-token (the windowed TTFT
        p90, which tracks queueing + prefill under load) must overrun
        the remaining budget: a backed-up engine stops doing work
        nobody will read.  The predictor is the WINDOWED percentile,
        not the old lifetime EMA, so it decays to zero within
        `_ttft_window_s` of the load ending — the PR-10 busy gate
        (which existed only because a storm-inflated, never-decaying
        EMA would otherwise shed from an idle engine forever) is
        retired with it.  Sheds are breaker-NEUTRAL downstream (the
        router classifies DeadlineExceededError as neutral, PR-1
        convention): an overloaded-but-reachable replica must not
        accrue breaker failures for honest sheds."""
        if deadline is None or fut.done():
            return False
        now = _time.monotonic()
        pred = self._ttft_p90()
        if now >= deadline:
            self._shed_expired += 1
            why = "deadline already expired in queue"
            reason = "shed_expired"
        elif pred > 0.0 and now + pred >= deadline:
            self._shed_predicted += 1
            why = (f"predicted TTFT ({pred * 1e3:.0f} ms windowed p90) "
                   "exceeds the remaining budget")
            reason = "shed_predicted"
        else:
            return False
        self._refused(reason, t_submit, _time.time(), tk, tokens_in)
        fut.set_exception(DeadlineExceededError(
            f"shed before prefill: {why}",
            timeout_s=max(0.0, deadline - now),
        ))
        return True

    def _alloc_or_evict(self, n: int) -> Optional[List[int]]:
        own = self._pool.alloc(n)
        if own is None and self._radix is not None:
            self._radix.evict(n - self._pool.free_blocks)
            own = self._pool.alloc(n)
        return own

    def _plan(self, prompt: List[int], n_new: int, fut: Future,
              t_submit: float, tk=None,
              fields: Optional[Dict] = None) -> Optional[_Plan]:
        """Admission's first phase, on the host only: the radix match,
        the request's blocks, its slot and its `_active` entry.
        Returns None, without consuming anything, when the pool cannot
        cover the request right now: the caller requeues it."""
        bs = self.block_size
        T = len(prompt)
        # the row is live while its position is short of `stop`, on the
        # device (`decode_chunk`) as in the host's mirror `pos_host`;
        # the model says what a request needs (a token a step: the
        # highest KV index a WANTED token's step touches, T+n_new-2)
        stop = self._model.rows_needed(T, n_new)
        pos0 = self._model.first_pos(T)
        total_blocks = _cdiv(stop, bs)

        shared: List[int] = []
        path: List = []
        if self._radix is not None:
            shared, path = self._radix.match(prompt)
        # a request needs a slot AND its blocks; a cache with no paged
        # leaf holds no blocks, and the free slot is all it needs
        own = self._alloc_or_evict(
            total_blocks - len(shared)) if self._has_blocks else []
        if own is None:
            if self._radix is not None:
                self._radix.release(path)
            return None
        # the request holds a slot and its blocks; `_admitting` stamps
        # it again where the host starts on its prefill program
        t_admit = _time.time()
        slot = self._free.pop()
        # donate this prompt's full blocks to the radix cache (pinned
        # until completion) NOW, so that a later admission of this tick
        # shares them: its suffix prefill is dispatched behind the
        # program that writes them.  Blocks the trie adopts stop being
        # request-owned so completion doesn't double-free them
        own_set = list(own)
        if self._radix is not None:
            donatable = own[: max(0, (T - 1) // bs - len(shared))]
            path, adopted = self._radix.insert(prompt, path, donatable)
            if adopted:
                adopted_set = set(adopted)
                own_set = [b for b in own_set if b not in adopted_set]
        self._slot_blocks[slot] = shared + own
        self._active[slot] = req = {
            "fut": fut, "out": [], "want": n_new,
            "since": self._chunk_seq + 1,  # first chunk with its steps
            "last": None,  # ... and the last, once handed off
            "pos_host": pos0, "stop": stop,
            "own_blocks": own_set, "tree_path": path,
            # the model's own fields of the request; where the device
            # counts (`device_counts`): where the row started, the
            # positions it has moved, the prompt's tokens its first
            # block hands back, what the device said of its answer
            "fields": fields or {}, "pos0": pos0, "moved": 0,
            "skip": T - pos0, "decided_at": [], "forwards": 0,
            "tk": tk, "tokens_in": T, "tokens_hit": len(shared) * bs,
            "harvests": 0, "t_submit": t_submit, "t_admit": t_admit,
            "t_prefill": t_admit, "t_first": None, "prefill_rows": 0,
            "prefill_chunks": 0,
        }
        return _Plan(req, slot, prompt, shared, own)

    def _pack_size(self, used: int) -> int:
        """The smallest packed program that holds `used` tokens."""
        return next(n for n in self._pack_sizes if n >= used)

    def _prefill(self, plans: List[_Plan]) -> None:
        """Admission's second phase: the planned requests' prefills,
        dispatched (async; nothing is read back).  Those with no cached
        prefix are packed, in arrival order, into as few programs as
        hold them (a program holds `_pack_rows` prompts and the largest
        pack size of tokens, each prompt in whole blocks); then each
        prefix hit prefills its suffix, one program a request, behind
        whatever wrote the blocks it shares.  A model that packs its
        suffixes has ONE path for all of them (`_prefill_behind`)."""
        if self._model.packs_suffixes:
            return self._prefill_behind(plans)
        bs = self.block_size
        cap = self._pack_sizes[-1]
        # a prompt no packed program holds goes chunk by chunk
        chunked = {id(p) for p in plans
                   if not p.shared and len(p.prompt) > cap}
        pack: List[_Plan] = []
        used = 0
        for plan in [p for p in plans
                     if not p.shared and id(p) not in chunked] + [None]:
            need = 0 if plan is None else _cdiv(len(plan.prompt), bs) * bs
            if pack and (plan is None or used + need > cap
                         or len(pack) == self._pack_rows):
                self._run_packed(self._pack_size(used), pack)
                pack, used = [], 0
            if plan is not None:
                pack.append(plan)
                used += need
        for plan in plans:
            if plan.shared or id(plan) in chunked:
                self._run_suffix(plan)

    def _prefill_behind(self, plans: List[_Plan]) -> None:
        """The planned requests' prefills where the model packs its
        suffixes: what no cached prefix covers of each prompt (behind a
        hit, or all of it), cut into parts of at most the largest pack
        size, the parts in arrival order end to end in as few programs
        as hold them (`_pack_rows` parts and the largest size of
        tokens, each part from a multiple of `_pack_align`).  A tick's
        hits share one read of the weights; a long prompt's chunks are
        programs of their own, each behind the one before."""
        bs, cap, align = self.block_size, self._pack_sizes[-1], \
            self._pack_align
        pack: List[_Part] = []
        used = 0
        for plan in plans:
            T, P = len(plan.prompt), len(plan.shared) * bs
            starts = range(P, T, cap)
            plan.req["prefill_chunks"] = len(starts)
            for lo in starts:
                part = _Part(plan, lo, min(T, lo + cap))
                need = _cdiv(part.hi - lo, align) * align
                if pack and (used + need > cap
                             or len(pack) == self._pack_rows):
                    self._run_suffixes(self._pack_size(used), pack)
                    pack, used = [], 0
                pack.append(part)
                used += need
            self._hit_tokens += P
            self._prefix_hits += bool(P)
            self._prefill_rows += 1
        if pack:
            self._run_suffixes(self._pack_size(used), pack)

    def _admitting(self, plans: List[_Plan]) -> None:
        """Queue wait ends HERE, where the host starts on the program
        that prefills these requests: behind the tick's earlier
        programs, as a request's one program always was.  Everything
        up to `_prefilled` is prefill dispatch."""
        t_admit = _time.time()
        for plan in plans:
            plan.req["t_admit"] = plan.req["t_prefill"] = t_admit

    def _prefilled(self, plans: List[_Plan]) -> None:
        """Stamps the requests whose prefill was just dispatched, in
        one program: it computes async on the device, so this is when
        the host let go of it."""
        t_prefill = _time.time()
        for req in (plan.req for plan in plans):
            req["t_prefill"] = t_prefill
            req["prefill_rows"] = len(plans)
            if req["tk"] is not None:
                req["tk"].admitted(req["t_admit"])
                req["tk"].prefilled(t_prefill)

    def _pack_arrays(self, N: int, pack: List[_Plan]) -> tuple:
        """A packed prefill's host-made arguments, `(tokens, seg, posn,
        blk_ids, last, slots, pos0, stop0)`: the prompts of `pack` end
        to end in a row of `N` tokens, each from a block boundary.  A
        cache with no paged leaf has no `blk_ids`."""
        bs, K = self.block_size, self._pack_rows
        i32 = np.int32
        tokens, posn = np.zeros(N, i32), np.zeros(N, i32)
        seg = np.full(N, -1, i32)
        blk_ids = np.full(N // bs, SCRATCH_BLOCK, i32)
        last, pos0, stop0 = (np.zeros(K, i32) for _ in range(3))
        slots = np.full(K, self.slots, i32)  # out of range: dropped
        at = 0
        for i, plan in enumerate(pack):
            T = len(plan.prompt)
            nb = _cdiv(T, bs)
            tokens[at:at + T] = plan.prompt
            seg[at:at + T] = i
            posn[at:at + T] = np.arange(T)
            # only the blocks holding real tokens; garbage within the
            # last of them is masked by pos until decode overwrites it
            if self._has_blocks:
                blk_ids[at // bs:at // bs + nb] = plan.own[:nb]
            last[i], slots[i] = at + T - 1, plan.slot
            pos0[i], stop0[i] = T, plan.req["stop"]
            at += nb * bs
        extra = self._model.pack_extras(K, [plan.req for plan in pack])
        if not self._has_blocks:
            return (tokens, seg, posn, last, slots, pos0, stop0, *extra)
        return (tokens, seg, posn, blk_ids, last, slots, pos0, stop0, *extra)

    def _suffix_arrays(self, N: int, pack: List[_Part]) -> tuple:
        """A packed suffix prefill's host-made arguments, `(tokens,
        posn, tables, last, slots, pos0, stop0)`: the parts of `pack`
        end to end in a row of `N` tokens, each from a multiple of
        `_pack_align`, at the positions of its own sequence (padding:
        -1); every query block of `_pack_align` rows with its request's
        row of the block table.  Only a part that ends its prompt picks
        a token and sets its slot."""
        align, K = self._pack_align, self._pack_rows
        i32 = np.int32
        tokens, posn = np.zeros(N, i32), np.full(N, -1, i32)
        tables = np.full((N // align, self._max_seq_blocks), SCRATCH_BLOCK,
                         i32)
        last, pos0, stop0 = (np.zeros(K, i32) for _ in range(3))
        slots = np.full(K, self.slots, i32)  # out of range: dropped
        at = 0
        for i, (plan, lo, hi) in enumerate(pack):
            blocks = plan.shared + plan.own
            nq = _cdiv(hi - lo, align)
            tokens[at:at + hi - lo] = plan.prompt[lo:hi]
            posn[at:at + hi - lo] = np.arange(lo, hi)
            tables[at // align:at // align + nq, :len(blocks)] = blocks
            if hi == len(plan.prompt):
                last[i], slots[i] = at + hi - lo - 1, plan.slot
                pos0[i], stop0[i] = hi, plan.req["stop"]
            at += nq * align
        return tokens, posn, tables, last, slots, pos0, stop0

    def _launch_packed(self, arrays: tuple, **held) -> None:
        """The packed program of size `held["N"]` on `arrays`, the cache
        and the slots' device state rebound to what it returns."""
        out = self._launch(
            self._prefill_packed_for(held["N"]), self.params, *self._cache,
            *arrays, self._pos, self._tok, self._stop, **held)
        self._cache = tuple(out[:-3])
        self._pos, self._tok, self._stop = out[-3:]

    def _run_packed(self, N: int, pack: List[_Plan]) -> None:
        """ONE program for the prompts of `pack`, end to end in a row
        of `N` tokens, each from a block boundary: their KV into their
        own blocks, their first tokens picked on the device, their
        slots' `pos` / `tok` / `stop` set.  An empty pack is the
        warm-up: padding only, into the scratch block."""
        real = sum(len(plan.prompt) for plan in pack)
        held = dict(N=N, rows=len(pack), tokens=real,
                    attended_pairs=attended_pairs(
                        (0, len(plan.prompt)) for plan in pack))
        with self._phase("prefill", **held):
            self._admitting(pack)
            self._launch_packed(self._pack_arrays(N, pack), **held)
            self._prefilled(pack)
        if pack:
            self._prefill_calls += 1
            self._prefill_rows += len(pack)
            self._prefill_tokens += real
            self._prefill_padded_tokens += N

    def _run_suffixes(self, N: int, pack: List[_Part]) -> None:
        """ONE program for the parts of `pack`, each behind its own
        request's blocks through the block table: their rows into the
        requests' blocks, and for a part that ends its prompt the first
        token picked on the device and the slot's `pos` / `tok` /
        `stop` set.  An empty pack is the warm-up: padding only, which
        is written nowhere."""
        plans = [part.plan for part in pack]
        real = sum(part.hi - part.lo for part in pack)
        held = dict(N=N, rows=len(pack), tokens=real,
                    attended_pairs=attended_pairs(
                        (part.lo, part.hi) for part in pack))
        with self._phase("prefill", **held):
            self._admitting(plans)
            self._launch_packed(self._suffix_arrays(N, pack), **held)
            self._prefilled(plans)
        if pack:
            self._prefill_calls += 1
            self._prefill_tokens += real
            self._prefill_padded_tokens += N

    def _run_suffix(self, plan: _Plan) -> None:
        """The part of a prompt no cached prefix covers (all of it, for
        a prompt too long for a packed program), prefilled behind the
        request's own blocks in chunks of at most `prefill_chunk`
        tokens, one program pair a chunk; the last sets the slot's
        state."""
        bs = self.block_size
        T, P = len(plan.prompt), len(plan.shared) * bs
        step = self._prefill_chunk or T - P
        starts = range(P, T, step)
        # a per-slot leaf the model carries rides in the chunk's program
        run = (self._run_state_chunk if self._model.state_carries_chunks
               else self._run_suffix_chunk)
        for i, lo in enumerate(starts):
            run(plan, lo, min(T, lo + step), i, len(starts))
        plan.req["prefill_chunks"] = len(starts)
        self._hit_tokens += P
        self._prefix_hits += bool(P)
        self._prefill_rows += 1

    def _run_suffix_chunk(self, plan: _Plan, lo: int, hi: int, i: int,
                          n: int) -> None:
        """Tokens `lo .. hi` of a prompt (from a block boundary),
        attending over the gathered blocks of the tokens before them
        (pow-2 buckets on both axes); then their KV into the request's
        blocks and, after the prompt's last chunk, the slot's state."""
        jnp = self._jnp
        bs = self.block_size
        req, slot, prompt, shared, own = plan
        blocks = shared + own
        before = blocks[:lo // bs]
        S, last = hi - lo, i == n - 1
        # RIGHT-pad (the scheme depends on it: causal prefill keeps the
        # real positions correct, the pad tail's garbage KV is masked
        # by the starting pos and overwritten as decoding advances)
        bucket = min(_next_pow2(S), self.max_len - 1)
        held = dict(N=bucket, rows=1, tokens=S,
                    attended_pairs=attended_pairs([(lo, hi)]))
        with self._phase("prefill", bucket=bucket, slot=slot,
                         hit_blocks=len(before), chunk=f"{i + 1}/{n}",
                         **held):
            self._admitting([plan])
            p_bucket = _next_pow2(len(before))
            blk_ids = jnp.asarray(
                before + [SCRATCH_BLOCK] * (p_bucket - len(before)),
                jnp.int32,
            )
            suffix = jnp.asarray(
                [prompt[lo:hi] + [0] * (bucket - S)], jnp.int32
            )
            logits, *kv = self._launch(
                self._suffix_prefill_for(bucket, p_bucket),
                self.params, *self._cache, suffix, blk_ids,
                jnp.asarray(lo, jnp.int32), **held,
            )
            # first generated token comes from the LAST REAL prompt
            # position; it STAYS on device — the next chunk emits it in
            # its pre-chunk token row.  A chunk that is not the prompt's
            # last picks nothing and sets no slot: an index past the
            # last is dropped
            tok0 = (jnp.argmax(logits[S - 1], axis=-1).astype(jnp.int32)
                    if last else jnp.zeros((), jnp.int32))
            # the chunk starts at a block boundary; write only the
            # blocks holding real tokens
            nb_real = _cdiv(S, bs)
            out = self._launch(
                self._write_blocks_for(bucket, nb_real),
                *self._cache, *kv,
                jnp.asarray(blocks[lo // bs:lo // bs + nb_real], jnp.int32),
                jnp.asarray(slot if last else self.slots, jnp.int32),
                jnp.asarray(hi, jnp.int32),
                tok0, self._pos, self._tok,
                jnp.asarray(req["stop"], jnp.int32), self._stop,
                # (the write attends nothing)
                N=bucket, rows=1, tokens=S,
            )
            self._cache = tuple(out[:-3])
            self._pos, self._tok, self._stop = out[-3:]
            self._prefilled([plan])
        self._prefill_calls += 1
        self._prefill_tokens += S
        self._prefill_padded_tokens += bucket

    def _run_state_chunk(self, plan: Optional[_Plan], lo: int, hi: int,
                         i: int, n: int, N: Optional[int] = None) -> None:
        """Tokens `lo .. hi` of a prompt in ONE program that takes the
        whole cache, the slot's per-slot leaves with the request's
        blocks, and hands it back (`engine_model`'s `chunk_prefill`):
        the rows into the request's blocks through its row of the table,
        the slot's leaves as they stand at `hi`, and after the prompt's
        last chunk the slot's state.  `plan` None is the warm-up: no
        token, written nowhere."""
        i32 = np.int32
        S, last = hi - lo, plan is not None and i == n - 1
        N = N or self._pack_size(S)
        tokens = np.zeros(N, i32)
        table = np.full(self._max_seq_blocks, SCRATCH_BLOCK, i32)
        slot = self.slots  # past the last: dropped
        if plan is not None:
            tokens[:S] = plan.prompt[lo:hi]
            blocks = plan.shared + plan.own
            table[:len(blocks)] = blocks
            slot = plan.slot
        plans = [] if plan is None else [plan]
        held = dict(N=N, rows=len(plans), tokens=S,
                    attended_pairs=attended_pairs([(lo, hi)]))
        with self._phase("prefill", slot=slot, chunk=f"{i + 1}/{n}",
                         **held):
            self._admitting(plans)
            out = self._launch(
                self._chunk_prefill_for(N), self.params, *self._cache,
                tokens, table, i32(slot), i32(lo), i32(S),
                i32(slot if last else self.slots), i32(hi),
                i32(plan.req["stop"] if last else 0),
                self._pos, self._tok, self._stop, **held)
            self._cache = tuple(out[:-3])
            self._pos, self._tok, self._stop = out[-3:]
            self._prefilled(plans)
        if plan is not None:
            self._prefill_calls += 1
            self._prefill_tokens += S
            self._prefill_padded_tokens += N
            self._state_chunks_resumed += int(lo > 0)

    def _release(self, slot: int, req: Dict):
        self._slot_blocks[slot] = []
        self._free.append(slot)
        if self._radix is not None and req["tree_path"]:
            self._radix.release(req["tree_path"])
        self._pool.free(req["own_blocks"])

    def _hand_off(self, slots: List[int]) -> None:
        """The rows of `slots` reached their `stop` in the chunk just
        dispatched, which the host KNOWS because its mirror of `pos` is
        exact for this model: their requests leave `_active` for
        `_handed`, where that chunk's harvest resolves them, and slot,
        blocks and trie path go back NOW, a chunk before that harvest,
        so that the next tick's admission budget counts the slot.

        THE ONE PLACE THAT RELIES ON LAUNCH ORDER.  Every program is
        launched from this thread, in program order, and takes the whole
        cache and the slots' `pos` / `tok` / `stop` from the program
        before it (donated: a data dependence, not only a stream).  The
        chunk that holds these rows' last steps is already launched
        (`last`), so whatever a later admission launches (a prefill into
        a freed block or an evicted trie block, the slot's per-slot
        state, its `pos` / `tok` / `stop`) runs strictly after the
        row's last read and last write.  Until somebody takes the slot
        it stays dead on the device (`pos == stop`) behind a zero row of
        the table, and writes nothing."""
        for slot in slots:
            req = self._active.pop(slot)
            req["last"] = self._chunk_seq
            self._handed.append((slot, req))
            self._release(slot, req)
        self._handoffs_total += len(slots)

    # -- engine loop ---------------------------------------------------
    def _gather_width(self) -> int:
        """Blocks per slot the next chunk must see: covers every active
        slot's highest touched index, which stops short of the row's
        `stop` (its allocation ends there too)."""
        need = 1
        for req in self._active.values():
            hi = min(req["pos_host"] + self._model.reach, req["stop"]) - 1
            need = max(need, hi // self.block_size + 1)
        return min(_next_pow2(need), self._max_seq_blocks)

    def _harvest(self, toks_host: np.ndarray, seq: int):
        """toks_host [token rows, slots] from dispatch `seq`: append per
        active slot what the MODEL says the chunk holds for it
        (`engine_model.harvested`), finish those that reached their
        budget.  Slots admitted after `seq` was dispatched are skipped —
        their tokens start in a later chunk.  A one-token model: row 0 =
        pre-chunk tokens; a request's FIRST chunk contributes from row
        0 (its prefill token rode along), later chunks from row 1.  A
        model whose device counts (`device_counts`): as many tokens as
        the device says it output, the first of them the prompt's
        own tail (`skip`), with the step each was decided at and the
        row's forwards; the host's mirror of `pos` is set from the
        count (a bound again for the chunks in flight)."""
        now = _time.monotonic()  # ages the shed predictor's samples
        wall = _time.time()      # the lifecycle stamp of this harvest
        done = []
        for slot, req in [*self._handed, *self._active.items()]:
            # (a request handed off takes nothing past its last chunk:
            # the slot's next holder owns the column from there)
            if req["since"] > seq or (req["last"] is not None
                                      and seq > req["last"]):
                continue
            new, more = self._model.harvested(toks_host, slot,
                                              req["since"] == seq)
            lo = 0
            if more is not None:
                req["forwards"] += more["forwards"]
                req["moved"] += len(new)
                req["pos_host"] = min(req["stop"], (
                    req["pos0"] + req["moved"]
                    + self._model.advance * (self._chunk_seq - seq)))
                lo = min(req["skip"], len(new))
                req["skip"] -= lo
            need = req["want"] - len(req["out"])
            if need > 0 and len(new) > lo:
                req["out"].extend(int(t) for t in new[lo:lo + need])
                if more is not None:
                    req["decided_at"].extend(
                        int(t) for t in more["decided_at"][lo:lo + need])
                req["harvests"] += 1
            if req["out"] and req["t_first"] is None:
                req["t_first"] = wall
                ttft = wall - req["t_submit"]
                self._ttft_ema_s = (
                    ttft if self._ttft_ema_s == 0.0
                    else 0.8 * self._ttft_ema_s + 0.2 * ttft
                )
                self._ttft_samples.append((now, ttft))
                if req["tk"] is not None:
                    req["tk"].first_token(wall)
            if len(req["out"]) >= req["want"]:
                done.append((slot, req))
        finished = {id(req) for _, req in done}
        self._handed = [(slot, req) for slot, req in self._handed
                        if id(req) not in finished]
        for slot, req in done:
            if req["last"] is None:  # not handed off: released here
                del self._active[slot]
                self._release(slot, req)
            out = req["out"][:req["want"]]
            if self._model.device_counts:
                out = Generated(out)
                out.decided_at = req["decided_at"][:req["want"]]
                out.forwards = req["forwards"]
            self._record("ok", req["t_submit"], wall, req["tokens_in"],
                         req)
            if req["tk"] is not None:
                req["tk"].done(len(out), wall)
            if not req["fut"].done():
                req["fut"].set_result(out)

    def _phase(self, name: str, **stats) -> _Phase:
        """`with self._phase(name, **stats)`: the span `engine.<name>`
        with `stats`, and its time in the tick's `<name>_s`."""
        return _Phase(self._phase_s, name,
                      self._span("engine." + name, **stats))

    def _launch(self, fn, *args, **held):
        """Hands a program to the device (async) and returns what it
        returns, under ONE stamp: the span `engine.launch` (inside the
        open `engine.prefill` / `engine.dispatch`) with the program's
        name and `held`, what the caller says the program holds
        (LAUNCH_HELD), and a `_Launched` in the tick's list.  The
        tick's first launch ends the host's gap before it
        (`host_gap_s`), the names are what a stall lists as in flight,
        and a program that had to be traced and compiled here is
        counted in the tick's `compiles` (its call is never `blocked`:
        compiling is the host's own time)."""
        known = fn._cache_size()
        with _Phase(self._phase_s, "launch", self._span(
                "engine.launch", program=fn.__name__, **held)) as call:
            out = fn(*args)
        took = call.t_end - call.t0
        compiled = fn._cache_size() - known
        self._compiles += compiled
        self._launches.append(_Launched(
            fn.__name__, self._span.is_enabled(), call.t0, took,
            took > LAUNCH_BLOCKED_S and not compiled, held))
        return out

    def _tick(self, admissions: List[tuple], t_wall: float) -> None:
        """One engine tick: admit (shed, prefill) what was popped,
        dispatch the next chunk, harvest the previous one.  Requeued
        entries are taken OUT of `admissions` in place, so the caller's
        failure path fails only what this tick consumed."""
        jnp = self._jnp
        t0 = _time.perf_counter()
        with self._span("engine.admit"):
            # PLAN every popped admission in arrival order, on the host
            # only; then dispatch what was planned
            plans: List[_Plan] = []
            requeued = 0
            with self._phase("plan"):
                for i, (prompt, n_new, fut, ts, dl, tk, *fields) in \
                        enumerate(admissions):
                    # shed BEFORE the prefill dispatch: an expired (or,
                    # under load, predictably-expiring) request consumes
                    # neither a slot nor a KV block nor a compile
                    if self._maybe_shed(fut, dl, ts, len(prompt), tk):
                        self._pending_admissions -= 1
                        continue
                    with self._lock:
                        plan = self._plan(prompt, n_new, fut, ts, tk,
                                          *fields)
                    if plan is None:
                        # pool exhausted by LIVE sequences: wait for
                        # completions, preserving arrival order
                        requeued = len(admissions) - i
                        break
                    self._pending_admissions -= 1
                    plans.append(plan)
                if requeued:
                    with self._wake:
                        self._queue.extendleft(
                            reversed(admissions[-requeued:])
                        )
                        self._pending_admissions = 0
                    del admissions[-requeued:]
                else:
                    self._pending_admissions = 0
            self._prefill(plans)
        with self._lock:
            # 0 = nothing live (a live batch needs at least one block);
            # a cache with no paged leaf has no width: any live row is 1
            live_now = bool(self._active)
            W = (int(live_now) if not self._has_blocks
                 else self._gather_width() if live_now else 0)
            # the rows whose last wanted step lies in the chunk about to
            # be dispatched: their slots go back at its dispatch.  Not
            # where the device counts: the mirror is then only a bound
            # and a row's end is known at its harvest
            ending = [] if self._model.device_counts else [
                slot for slot, req in self._active.items()
                if req["pos_host"] + self._model.advance >= req["stop"]]
        toks = None
        # of the chunk's slots x chunk row-steps, those a request was
        # waiting for (its steps before its stop); the rest are dead
        row_steps = row_steps_live = rows_live = rows_flushed = 0
        contexts: List[int] = []  # of the rows live at its first step
        if W:
            with self._phase("dispatch", W=W, handed_off=len(ending)):
                tables = ()
                if self._has_blocks:
                    with self._lock:
                        table = np.zeros((self.slots, W), np.int32)
                        for slot in self._active:
                            blocks = self._slot_blocks[slot][:W]
                            table[slot, :len(blocks)] = blocks
                    tables = (jnp.asarray(table),)
                self._last_gather_blocks = W if self._has_blocks else 0
                row_steps = self.slots * self.chunk
                with self._lock:
                    # the host's mirror of the device's `pos`: a row
                    # advances while it is short of its stop (where the
                    # device counts: by no more than this, a bound that
                    # the harvest corrects)
                    for req in self._active.values():
                        end = min(req["pos_host"] + self._model.advance,
                                  req["stop"])
                        row_steps_live += end - req["pos_host"]
                        rows_live += end > req["pos_host"]
                        if end > req["pos_host"]:
                            contexts.append(req["pos_host"] + 1)
                        rows_flushed += end - req["pos_host"] == self.chunk
                        req["pos_host"] = end
                # (a chunk is no prefill: it pads nothing, `N` 0; its
                # live rows and the tokens they attend at its first step)
                out = self._launch(
                    self._chunk_step_for(W if self._has_blocks else 0),
                    self.params, *self._cache, *tables,
                    self._tok, self._pos, self._stop,
                    N=0, rows_live=rows_live, live_tokens=sum(contexts))
                self._cache = tuple(out[:-3])
                self._tok, self._pos, toks = out[-3:]
                if self._decode_kernel == "pallas":
                    self._decode_kernel_dispatches += 1
                else:
                    self._decode_gather_dispatches += 1
                self._chunk_seq += 1
                with self._lock:
                    self._hand_off(ending)
        # OVERLAP: harvest the PREVIOUS chunk's tokens while the
        # current chunk computes — the device->host read is round-trip
        # latency (measured at ~half the synced chunk wall time on an
        # earlier remote device), and the dispatch above is async, so
        # the read rides under the compute.  Cost: a request's tokens
        # reach its caller a chunk late; its SLOT went back at the
        # dispatch of its last chunk (`_hand_off`), except where the
        # device counts.
        model_fields: Dict[str, object] = {}
        t_read = None  # when the wait for the device returned
        if self._pending_toks is not None:
            p_toks, p_seq = self._pending_toks
            with self._span("engine.harvest"):
                # the BLOCKING read and nothing else: what the tick
                # waits for the device, apart from the host's own work
                with self._phase("device_wait") as waited:
                    toks_host = np.asarray(p_toks)
                t_read = waited.t_end
                with self._phase("harvest_host"):
                    if self._model.aux_rows:
                        rows = self._model.token_rows
                        model_fields = self._model.tick_fields(
                            toks_host[rows:])
                        toks_host = toks_host[:rows]
                        for k in self._model_sums:
                            self._model_sums[k] += model_fields[k]
                    with self._lock:
                        self._harvest(toks_host, p_seq)
        self._pending_toks = (
            (toks, self._chunk_seq) if toks is not None else None
        )
        self._close({
            "seq": self._chunk_seq,
            "t_wall": t_wall,  # wall clock at the tick's start
            "admitted": len(admissions),
            # requests whose slot went back at this tick's dispatch
            "handed_off": len(ending),
            "gather_blocks": W if self._has_blocks else 0,
            # rows that owed a token at the chunk's first step: for
            # per-slot leaves, the states its first step moves
            "state_rows_live": rows_live if self._has_state else 0,
            # the rows whose per-slot state the chunk WROTE: a row at
            # every step it was live, or, where the model defers the
            # write to the chunk's last step, the rows live at that one
            "state_rows_flushed": 0 if not self._has_state else (
                rows_flushed if self._model.state_write_deferred
                else row_steps_live),
            # (where the device counts, the harvested chunk's own count
            # comes with `model_fields`: the mirror's is of positions)
            "row_steps_live": (0 if self._model.device_counts
                               else row_steps_live),
            "row_steps": row_steps,
            # the model's own counters of the chunk harvested in this
            # tick (`engine_model.tick_fields`; none for Llama), and its
            # own fields of the dispatched chunk's live rows' contexts
            **model_fields,
            **(self._model.context_fields(contexts) if W else {}),
        }, t0, t_read)

    def _close(self, rec: Dict[str, object], t0: float,
               t_read: Optional[float]) -> None:
        """Closes the tick's account: the one record that goes to the
        ring, into the per-second account and, where the tick stalled,
        into `stalls`.  Every time in it comes from the phases' stamps.

        `cpu_s` / `proc_cpu_s` are this thread's and the whole
        process's CPU time since the tick before closed (the tick, the
        wait before it and the bookkeeping between): a long tick with no
        CPU anywhere waited on the device or the runtime, one with a
        CPU-second a second on a contended host or the GIL.

        A tick is `starved` when its wait for the previous chunk was
        under STARVED_WAIT_S, i.e. the chunk had ENDED before the host
        asked: the device may have run dry for as long as the host took
        from the previous tick's read to this tick's first program.
        `host_gap_s` is that time, an UPPER bound on what the device
        idled for the host; `gap_harvest_host_s` is its part before the
        tick (the harvest and bookkeeping of the tick before), `plan_s`
        the tick's plan, the rest its packing up to the launch.
        `wait_s` is no part of it: the loop blocks only while no
        sequence is live, which is idling for want of traffic."""
        t_end = _time.perf_counter()
        tick_s = t_end - t0
        ph, launches = self._phase_s, self._launches
        cpu = _cpu_now()
        rec.update(
            tick_s=tick_s,
            admit_s=ph["plan"] + ph["prefill"],
            dispatch_s=ph["dispatch"],
            harvest_s=ph["device_wait"] + ph["harvest_host"],
            cpu_s=cpu[0] - self._cpu_mark[0],
            proc_cpu_s=cpu[1] - self._cpu_mark[1],
            starved=(t_read is not None and bool(launches)
                     and ph["device_wait"] < STARVED_WAIT_S),
        )
        for name, key in _PHASE_KEYS:
            rec[key] = ph[name]
        rec.update(zip(LAUNCHED, (
            len(launches), ph["launch"],
            sum(call.took_s for call in launches if call.blocked))))
        if rec["starved"] and self._t_gap_from is not None:
            before = t0 - ph["wait"] - self._t_gap_from
            rec.update(zip(GAPS, (before + launches[0].t0 - t0, before)))
        if self._compiles:
            rec["compiles"] = self._compiles
        stalled = (not self._compiles and tick_s > STALL_MIN_S
                   and tick_s > STALL_FACTOR * self._tick_ema_s > 0.0)
        if stalled:
            rec["stalled"] = True
        self._tick_ema_s = (
            tick_s if self._tick_ema_s == 0.0
            else 0.8 * self._tick_ema_s + 0.2 * tick_s
        )
        with self._lock:  # keeps stats() and its snapshot whole
            unresolved = self._unresolved()
            rec.update(
                active=len(unresolved), queued=len(self._queue),
                live_tokens=sum(r["pos_host"] for r in unresolved))
            done = (self._prefill_calls, self._prefill_rows,
                    self._prefill_tokens, self._prefill_padded_tokens)
            if done[0] != self._prefilled_mark[0]:
                rec.update(zip(PREFILLED, (
                    n - m for n, m in zip(done, self._prefilled_mark))))
                self._prefilled_mark = done
            if self._hit_tokens != self._hit_mark:
                # the prompt tokens its admissions found cached
                rec["prefix_hit_tokens"] = self._hit_tokens - self._hit_mark
                self._hit_mark = self._hit_tokens
            if self._state_chunks_resumed != self._resumed_mark:
                rec["state_chunks_resumed"] = (
                    self._state_chunks_resumed - self._resumed_mark)
                self._resumed_mark = self._state_chunks_resumed
            before = self._tick_ring[-1] if self._tick_ring else None
            self._tick_ring.append(rec)
            self._account_add(rec)
            self._launch_account_add(launches)
            if self._stalls and self._stalls[-1]["after"] is None:
                # the tick AFTER a stall: did it wait as usual (the
                # device was late) or find its chunk done (it was not)
                self._stalls[-1] = {**self._stalls[-1], "after": rec}
            if stalled:
                self._stalls.append({
                    "before": before, "tick": rec, "after": None,
                    "in_flight": self._launched_before + [
                        call.program for call in launches]})
            self._stats_snapshot = self._stats_locked()  # fresh
        self._new_tick(cpu, t_read if t_read is not None else t_end)

    def _new_tick(self, cpu: tuple, t_gap_from: Optional[float]) -> None:
        """What the next tick's account starts from."""
        # (0, not 0.0: a phase the tick never entered is an int in its
        # record, which is 2 bytes of a pickle and not 9)
        self._phase_s = dict.fromkeys(_TICK_SUMS, 0)
        self._launched_before = [call.program for call in self._launches]
        self._launches = []
        self._compiles = 0
        self._cpu_mark = cpu
        self._t_gap_from = t_gap_from

    def _account_add(self, rec: Dict[str, object]) -> None:
        """Adds a closed tick to the account of the second it began
        in.  A second that ends is kept as a row of ints."""
        sec = int(rec["t_wall"])
        row = self._account_open
        if row is None or row[0] != sec:
            if row is not None:
                self._account.append(_account_row(row))
            row = self._account_open = [sec] + [0] * (
                len(_ACCOUNT_COLUMNS) - 1)
        row[1] += 1
        for i, k in enumerate(_ACCOUNT_KEYS, 2):
            row[i] += rec.get(k, 0)
        if "host_gap_s" in rec:
            row[_GAP_PLAN] += rec["plan_s"]

    def _launch_account_add(self, launches: List[_Launched]) -> None:
        """Adds a closed tick's launches to the account by program.  The
        first launch a NEW profiler session records drops the rows of
        the session before: the traced rows are one session's."""
        acct = self._launch_account
        for call in launches:
            if call.traced and not self._traced_last:
                for key in [k for k in acct if k[1]]:
                    del acct[key]
            self._traced_last = call.traced
            row = acct.setdefault((call.program, call.traced),
                                  [0] * (len(LAUNCH_HELD) + 3))
            row[0] += 1
            for i, k in enumerate(LAUNCH_HELD, 1):
                row[i] += call.held.get(k, 0)
            row[-2] += call.took_s
            row[-1] += call.blocked

    def _launch_account_rows(self) -> List[tuple]:
        return [(program, int(traced), *row[:-2], round(row[-2] * 1e6),
                 row[-1])
                for (program, traced), row in self._launch_account.items()]

    def _account_rows(self) -> List[tuple]:
        rows = list(self._account)
        if self._account_open is not None:
            rows.append(_account_row(self._account_open))
        return rows

    def _loop(self):
        # the account starts here, on the engine's own thread (the
        # warm-up ran its programs on the caller's)
        self._launches = []
        self._new_tick(_cpu_now(), None)
        while True:
            with self._wake:
                # (a request handed off waits for a chunk in flight:
                # the loop ticks on, dispatching nothing, to harvest it)
                while (self._running and not self._active
                       and not self._handed
                       and not (self._queue and self._free)):
                    # blocked before a tick: carried into the tick
                    # that follows (summed over the wake-ups)
                    with self._phase("wait"):
                        self._wake.wait()
                if not self._running:
                    # the engine thread sweeps its own state on exit:
                    # shutdown()'s sweep runs after a BOUNDED join, so
                    # an admission compile outlasting the join would
                    # otherwise register requests into _active AFTER
                    # that sweep and strand their futures forever
                    for item in self._queue:
                        if not item[2].done():
                            item[2].cancel()
                    self._queue.clear()
                    with self._lock:
                        self._cancel_unresolved()
                    return
                admissions = []
                # bound by the FREE SLOTS, not just the cap: _admit
                # consumes a slot per entry after this loop.  The cap
                # keeps one straggler admission from starving active
                # slots of decode ticks, but filling MATTERS — an
                # engine below full occupancy wastes its whole premise
                budget = min(ADMIT_BUDGET, len(self._free))
                while self._queue and len(admissions) < budget:
                    admissions.append(self._queue.popleft())
                self._pending_admissions = len(admissions)
            try:
                # `wall_ns` anchors the trace's clock (which starts at 0
                # with the profiler session) to the wall stamps of
                # tick_ring and request_ring
                wall_ns = _time.time_ns()
                with self._span("engine.tick", seq=self._chunk_seq,
                                active=(len(self._active)
                                        + len(self._handed)),
                                admitted=len(admissions),
                                wall_ns=wall_ns):
                    self._tick(admissions, wall_ns * 1e-9)
            except Exception as e:  # engine must not die silently
                logger.exception("llm engine tick failed; failing %d "
                                 "active request(s)",
                                 len(self._active) + len(self._handed))
                self._pending_toks = None
                # the failed tick leaves no record: its account is void
                self._new_tick(_cpu_now(), None)
                wall = _time.time()
                with self._lock:
                    for req in self._unresolved():
                        self._record("error", req["t_submit"], wall,
                                     req["tokens_in"], req)
                        if not req["fut"].done():
                            req["fut"].set_exception(e)
                    # admissions popped from the queue but not (yet)
                    # registered in _active would otherwise hang their
                    # callers forever
                    for _p, _n, fut, *_ in admissions:
                        if not fut.done():
                            fut.set_exception(e)
                    self._active.clear()
                    self._handed.clear()
                    self._free = list(range(self.slots))
                    self._slot_blocks = [[] for _ in range(self.slots)]
                    self._pending_admissions = 0
                    # host bookkeeping restarts from scratch: every
                    # block returns to the pool and the radix cache
                    # empties (its pinned paths died with the requests)
                    self._pool = BlockPool(self._pool.num_blocks,
                                           spec=self._pool.spec,
                                           slots=self.slots)
                    if self._radix is not None:
                        self._radix = RadixCache(
                            self.block_size, self._pool
                        )
                # the failed tick may have DONATED pool buffers without
                # ever rebinding them — rebuild the device state (every
                # cache leaf: sidecars are donated too) or every later
                # dispatch dies on invalid donated buffers
                self._cache = self._alloc_cache()
                self._reset_rows()
