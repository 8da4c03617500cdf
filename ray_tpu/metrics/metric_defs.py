"""Central metric-definitions catalog.

Reference: `src/ray/stats/metric_defs.h` — every core metric the system
emits is declared ONCE, in one table, with its type, help string, tag
keys, and (histograms) bucket boundaries.  Subsystems never invent
ad-hoc names: they call :func:`inc` / :func:`observe` / :func:`set_gauge`
with a cataloged name, and the accessor lazily instantiates the metric
in this process's registry on first touch.

Hot-path discipline: core instrumentation is OFF by default
(`RT_METRICS_ENABLED` / `Config.metrics_enabled`).  The record helpers
check one module flag and return — a disabled record costs a function
call and a bool test, which is what keeps the measured task-storm
overhead of the whole plane under the 3% budget (`perf.py --config
obs_overhead`, PERF.md).  Scrape-time refreshes (the dashboard's
builtin gauges, the serve stats bridge) bypass the gate — they run per
scrape, never per task.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

from ray_tpu.metrics.registry import Counter, Gauge, Histogram, Metric

# latency buckets: control-plane ops span ~100 us (owner hot path) to
# tens of seconds (lease negotiation against a saturated daemon)
_LATENCY_S = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
              0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)
# coarse work-unit buckets (shuffle partitions, train steps)
_WORK_S = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
           60.0, 300.0)

# name -> (type, help, tag_keys, boundaries-or-None)
CATALOG: Dict[str, Tuple[str, str, Tuple[str, ...], Optional[tuple]]] = {
    # ---- owner plane (core/runtime.py, core/completion.py) ----------
    "rt_owner_tasks_submitted_total": (
        "counter", "tasks registered with the owner plane", ("shard",),
        None),
    "rt_owner_tasks_completed_total": (
        "counter", "owner-side final task completions",
        ("shard", "outcome"), None),
    "rt_owner_task_retries_total": (
        "counter", "owner-side task retry resubmissions", ("shard",),
        None),
    "rt_owner_task_latency_seconds": (
        "histogram", "submit-to-final-completion wall latency",
        ("shard",), _LATENCY_S),
    "rt_owner_lease_latency_seconds": (
        "histogram", "request_lease round-trip against the node daemon",
        ("shard",), _LATENCY_S),
    "rt_owner_lease_grants_total": (
        "counter", "worker lease grants adopted", ("shard",), None),
    # ---- task-event feed (core/task_events.py) ----------------------
    "rt_task_events_dropped_total": (
        "counter", "task events dropped at the full buffer", (), None),
    # ---- object plane (core/noded.py, core/runtime.py) --------------
    "rt_object_store_used_bytes": (
        "gauge", "object store bytes in use", (), None),
    "rt_object_store_capacity_bytes": (
        "gauge", "object store capacity", (), None),
    "rt_object_store_objects": (
        "gauge", "sealed objects resident in the store", (), None),
    "rt_object_spilled_objects": (
        "gauge", "primary copies currently spilled to disk", (), None),
    "rt_object_spill_bytes_total": (
        "counter", "bytes spilled to disk (monotonic)", (), None),
    "rt_object_restore_bytes_total": (
        "counter", "bytes restored from disk (monotonic)", (), None),
    "rt_object_reconstructions_total": (
        "counter", "lost objects re-derived via lineage resubmit", (),
        None),
    # ---- object integrity + storage faults (core/noded.py,
    # core/diskio.py; these record rare FAILURE events, so their
    # call sites bypass the metrics_enabled gate) --------------------
    "rt_object_integrity_errors_total": (
        "counter", "checksum verification failures by path "
        "(restore | transfer | get | snapshot)", ("path",), None),
    "rt_object_quarantined_total": (
        "counter", "corrupt spilled files moved to quarantine", (),
        None),
    "rt_spill_disk_full_total": (
        "counter", "spill passes refused by the low-disk watermark or "
        "aborted by ENOSPC", (), None),
    "rt_spill_errors_total": (
        "counter", "disk I/O errors on the spill plane by op "
        "(spill | restore)", ("op",), None),
    # ---- shuffle (data/shuffle.py) ----------------------------------
    "rt_shuffle_partition_seconds": (
        "histogram", "wall time of one shuffle map/reduce task "
        "(admission to completion)", ("phase",), _WORK_S),
    "rt_shuffle_backpressure_total": (
        "counter", "shuffle admission stalls raised as "
        "BackPressureError", ("phase",), None),
    "rt_shuffle_rows_total": (
        "counter", "rows entering the shuffle map phase", (), None),
    # ---- serve (bridged from engine/replica stats(), scrape-time) ---
    "rt_serve_engine_queue_depth": (
        "gauge", "engine queue depth (active + queued + pending "
        "admissions)", ("app", "deployment", "replica"), None),
    "rt_serve_engine_block_occupancy": (
        "gauge", "KV block pool occupancy fraction",
        ("app", "deployment", "replica"), None),
    "rt_serve_engine_prefix_hit_rate": (
        "gauge", "radix prefix cache hit rate over served tokens",
        ("app", "deployment", "replica"), None),
    "rt_serve_engine_ttft_ema_seconds": (
        "gauge", "time-to-first-token EMA",
        ("app", "deployment", "replica"), None),
    "rt_serve_engine_ttft_p90_seconds": (
        "gauge", "windowed time-to-first-token p90 (decays; feeds "
        "shedding + SLO autoscaling)",
        ("app", "deployment", "replica"), None),
    "rt_serve_engine_rejected_total": (
        "gauge", "engine admission rejections (monotonic, bridged)",
        ("app", "deployment", "replica"), None),
    "rt_serve_engine_shed_total": (
        "gauge", "deadline sheds before prefill (monotonic, bridged)",
        ("app", "deployment", "replica"), None),
    "rt_serve_kv_pool_bytes": (
        "gauge", "resident KV block-pool payload bytes (K+V, "
        "excluding the int8 f32 scale sidecar)",
        ("app", "deployment", "replica"), None),
    "rt_serve_decode_kernel_total": (
        "gauge", "decode ticks dispatched through the fused paged-"
        "attention kernel (monotonic, bridged; gather-fallback ticks "
        "are the engine's decode_gather_dispatch_total)",
        ("app", "deployment", "replica"), None),
    # ---- serve request ledger (serve/request_ledger.py; windowed
    # per-request phase latencies replacing EMA-only reporting) -------
    "rt_serve_ttft_seconds": (
        "histogram", "request time-to-first-token (submit to first "
        "harvested token)", ("app", "deployment", "replica"),
        _LATENCY_S),
    "rt_serve_tpot_seconds": (
        "histogram", "mean time per output token after the first "
        "(decode cadence)", ("app", "deployment", "replica"),
        _LATENCY_S),
    "rt_serve_queue_wait_seconds": (
        "histogram", "router assignment wait (request arrival to "
        "replica pick)", ("app", "deployment", "replica"), _LATENCY_S),
    "rt_serve_prefill_seconds": (
        "histogram", "engine prefill wall time (admission to KV "
        "residency)", ("app", "deployment", "replica"), _LATENCY_S),
    "rt_serve_e2e_seconds": (
        "histogram", "end-to-end request latency at the ledger origin "
        "(proxy arrival or replica entry to terminal phase)",
        ("app", "deployment", "replica"), _LATENCY_S),
    # ---- rllib (rllib/env/env_runner_group.py, algorithms/ppo.py) ---
    "rt_rllib_env_steps_total": (
        "counter", "env steps consumed by the learner side (ledger-"
        "recorded, exactly once per sample batch)", (), None),
    "rt_rllib_sample_batch_bytes_total": (
        "counter", "sample-batch payload bytes fetched from the object "
        "plane", (), None),
    "rt_rllib_learner_update_seconds": (
        "histogram", "wall time of one full learner update pass "
        "(all epochs over one train batch)", (), _WORK_S),
    "rt_rllib_env_runners": (
        "gauge", "env-runner fleet size (replacements keep it at "
        "target; 0 after stop)", (), None),
    # ---- compiled DAGs (dag/execution.py, dag/channel.py) -----------
    "rt_dag_execs_total": (
        "counter", "completed executions per resident exec loop "
        "(one inc per full pass over the actor's compiled steps)", (),
        None),
    "rt_dag_channel_write_seconds": (
        "histogram", "wall time of one channel slot publication "
        "(acquire + copy + seal; includes the spill put for oversized "
        "payloads and the daemon relay for cross-node writes)", (),
        _LATENCY_S),
    "rt_dag_channel_ring_full_total": (
        "counter", "channel writes that blocked on (or timed out "
        "against) a full ring — the reader is lagging more than "
        "dag_ring_slots messages behind", (), None),
    # ---- train (train/trainer.py) -----------------------------------
    "rt_train_step_seconds": (
        "histogram", "wall time between delivered training result "
        "rounds", (), _WORK_S),
    "rt_train_elastic_events_total": (
        "counter", "elastic lifecycle transitions (shrink / reform / "
        "regrow)", ("kind",), None),
    # ---- observability plane itself ---------------------------------
    "rt_obs_frames_sent_total": (
        "counter", "batched obs frames shipped to the controller", (),
        None),
    "rt_trace_spans_dropped_total": (
        "counter", "finished spans dropped at the full export queue",
        (), None),
}

_lock = threading.Lock()
_instances: Dict[str, Metric] = {}

# Core-path gate.  Read once from the environment at import (workers
# inherit RT_METRICS_ENABLED through the daemon spawn chain exactly
# like the tracing flag); flip at runtime with set_enabled().
_enabled = os.environ.get("RT_METRICS_ENABLED", "") in ("1", "true", "True")


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool):
    """Flip core-path instrumentation for THIS process; also mirrors
    the env flag so children spawned after the flip inherit it."""
    global _enabled
    _enabled = bool(on)
    if on:
        os.environ["RT_METRICS_ENABLED"] = "1"
    else:
        os.environ.pop("RT_METRICS_ENABLED", None)


def metric(name: str) -> Metric:
    """The process-local instance of a cataloged metric (lazy,
    singleton).  Raises KeyError for names outside the catalog — the
    whole point is that core metric names exist in one table."""
    m = _instances.get(name)
    if m is not None:
        return m
    with _lock:
        m = _instances.get(name)
        if m is not None:
            return m
        typ, help_, tag_keys, boundaries = CATALOG[name]
        if typ == "counter":
            m = Counter(name, help_, tag_keys=tag_keys)
        elif typ == "gauge":
            m = Gauge(name, help_, tag_keys=tag_keys)
        else:
            m = Histogram(name, help_, boundaries=boundaries or (),
                          tag_keys=tag_keys)
        _instances[name] = m
        return m


# -- gated record helpers (the core hot paths call these) --------------
def inc(name: str, value: float = 1.0,
        tags: Optional[Dict[str, str]] = None):
    if not _enabled:
        return
    metric(name).inc(value, tags=tags)


def observe(name: str, value: float,
            tags: Optional[Dict[str, str]] = None):
    if not _enabled:
        return
    metric(name).observe(value, tags=tags)


def set_gauge(name: str, value: float,
              tags: Optional[Dict[str, str]] = None):
    if not _enabled:
        return
    metric(name).set(value, tags=tags)
