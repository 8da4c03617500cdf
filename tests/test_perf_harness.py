"""Smoke test for the runtime microbenchmark harness (reference:
`ray microbenchmark`, `_private/ray_perf.py`).  Runs a fast subset with
tiny durations — validates the harness end-to-end, not the numbers."""

import json

import pytest


def test_perf_harness_subset(tmp_path):
    from ray_tpu.scripts.perf import main

    out = tmp_path / "perf.json"
    results = main([
        "--filter", "client tasks sync",
        "--rounds", "1",
        "--round-sec", "0.2",
        "--num-workers", "2",
        "--json", str(out),
    ])
    assert "single client tasks sync" in results
    assert results["single client tasks sync"]["ops_per_s"] > 0
    saved = json.loads(out.read_text())
    assert saved == results


def test_perf_harness_actor_row():
    from ray_tpu.scripts.perf import main

    results = main([
        "--filter", "1:1 actor calls sync",
        "--rounds", "1",
        "--round-sec", "0.2",
        "--num-workers", "2",
    ])
    assert results["1:1 actor calls sync"]["ops_per_s"] > 0


def test_core_split_accounting():
    """--core-split: per-plane CPU accounting is internally consistent
    (planes identified, per-task costs positive, projection computed)."""
    from ray_tpu.scripts.perf import main

    results = main([
        "--filter", "ZZZNONE",  # skip the matrix; core-split only
        "--core-split",
        "--storm-n", "300",
        "--num-workers", "2",
    ])
    split = results["core_split"]
    assert split["measured_tasks_per_s"] > 0
    assert split["projected_pipelined_tasks_per_s"] > 0
    # the storm burned driver + worker CPU; the daemon plane is cheap
    assert split["driver_us_per_task"] > 0
    assert split["worker_us_per_task"] > 0
    assert split["bottleneck"] in ("driver", "noded", "worker_pool")


def test_engine_trace_smoke_rows():
    """`--engine-trace`: the serve_llm_cb regression canary plus the
    paged-KV acceptance rows, structurally validated (timing claims
    live in PERF.md, measured on an idle box):
    - budget invariance: the over-provisioned pool runs the SAME
      compiled chunk programs as the workload-sized one (equal gather
      widths) — the mechanism that kills the ring-size tax;
    - radix reuse: prefix_on prefills strictly fewer tokens than
      prefix_off on the shared-system-prompt workload."""
    from ray_tpu.scripts.perf import main

    results = main(["--engine-trace", "--engine-requests", "12"])
    smoke = results["serve_llm_cb_smoke"]
    assert smoke["tokens_per_sec"] > 0
    assert smoke["ticks"] > 0
    assert results["sized"]["gather_blocks"] == \
        results["overprovisioned"]["gather_blocks"] > 0
    assert results["overprovisioned"]["kv_budget_tokens"] > \
        5 * results["sized"]["kv_budget_tokens"]
    assert results["prefix_on"]["prefix_hit_tokens"] > 0
    assert results["prefix_on"]["prefill_tokens"] < \
        results["prefix_off"]["prefill_tokens"]
    assert results["prefix_off"]["prefix_hit_tokens"] == 0


def test_decode_kernel_rows():
    """`--config decode_kernel`: the fused paged-attention rows,
    structurally validated (CPU interpret-mode timings are not speed
    claims — see PERF.md):
    - the kernel rows really dispatched the Pallas plane (kernel
      ticks > 0, zero gather-fallback ticks) and vice versa;
    - both routes completed the same workload (equal tick counts at
      equal batch);
    - the int8 pool sits at exactly half the bf16 payload bytes at
      the same block budget, scale sidecar priced separately."""
    from ray_tpu.scripts.perf import main

    results = main(["--config", "decode_kernel",
                    "--decode-batches", "4", "--kernel-interpret"])
    pal, gat = results["decode_b4_pallas"], results["decode_b4_gather"]
    assert pal["decode_kernel"] == "pallas"
    assert pal["kernel_ticks"] > 0 and pal["fallback_ticks"] == 0
    assert gat["decode_kernel"] == "gather"
    assert gat["kernel_ticks"] == 0 and gat["fallback_ticks"] > 0
    assert pal["tokens_per_sec"] > 0 and gat["tokens_per_sec"] > 0
    assert pal["ticks"] == gat["ticks"] > 0
    occ = results["kv_pool_occupancy"]
    assert occ["int8_payload_ratio"] == 0.5
    assert occ["kv_scale_bytes_int8"] > 0 == occ["kv_scale_bytes_fp"]


def test_elastic_recovery_row():
    """`--elastic-recovery`: the elastic-training MTTR canary —
    structurally validated like the engine-trace rows (measured
    latencies live in PERF.md):
    - the kill was detected through the health plane (detect_s bounded)
      and exactly one failover recovered it;
    - recovery resumed at (or before) the kill step from the latest
      atomic checkpoint, never beyond it;
    - the run finished every step without consuming the failure
      budget (fit() returned without error at max_failures=0)."""
    from ray_tpu.scripts.perf import main

    results = main(["--elastic-recovery", "--elastic-steps", "10"])
    row = results["elastic_recovery"]
    assert row["failovers"] == 1.0
    assert 0.0 < row["detect_s"] < row["mttr_s"]
    assert 0.0 < row["resume_step"] <= row["kill_step"]
    assert row["final_step"] == 9.0  # every step delivered
    assert row["reform_width"] == 2.0  # capacity returned: full width


def test_overload_row():
    """`--overload`: the overload-plane acceptance rows, structurally
    validated like the engine-trace rows (wall-clock numbers live in
    PERF.md):
    - exact admission accounting: every offered request is admitted,
      rejected, or shed — exactly once — and both overload outcomes
      actually occurred under the storm;
    - sheds never reach prefill (requests prefilled == admissions, in
      no more programs than that) and the queue never exceeds its cap;
    - the KV block pool returns to its pre-storm free count;
    - TTFT percentiles under 2x overload are well-formed."""
    from ray_tpu.scripts.perf import main

    results = main(["--overload"])
    storm = results["overload_storm"]
    assert storm["offered"] == (storm["admitted"] + storm["rejected"]
                                + storm["shed"])
    assert storm["rejected"] > 0 and storm["shed"] > 0
    assert storm["shed"] == storm["shed_expired"] + storm["shed_predicted"]
    assert storm["prefill_rows"] == storm["admitted"]
    assert 0 < storm["prefill_calls"] <= storm["prefill_rows"]
    assert storm["queue_peak"] <= storm["queue_cap"]
    assert storm["blocks_free_delta"] == 0
    assert storm["admitted_tok_s"] > 0
    ttft = results["overload_ttft"]
    assert 0 < ttft["ttft_p50_ms"] <= ttft["ttft_p99_ms"]
    assert ttft["concurrency"] == 2 * 4.0  # 2x the engine's slots


def test_data_shuffle_row():
    """`--config data_shuffle`: the over-memory shuffle acceptance row,
    structurally validated (throughput numbers live in PERF.md):
    - the dataset really exceeded the store (2x budget) and the
      exchange completed THROUGH spilling (spill_bytes > 0);
    - exact row accounting: every input row came out exactly once
      (count + checksum), globally sorted — no single-task AllToAll
      gather barrier could survive this store budget."""
    from ray_tpu.scripts.perf import main

    results = main([
        "--config", "data_shuffle",
        "--shuffle-rows", "3200000",
        "--shuffle-store-mb", "12",
    ])
    row = results["data_shuffle"]
    assert row["rows_per_s"] > 0
    assert row["store_ratio"] >= 2.0
    assert row["spill_bytes"] > 0
    assert row["rows_out"] == row["rows"]
    assert row["rows_exact"] == 1.0
    assert row["globally_sorted"] == 1.0


def test_obs_overhead_row():
    """`--config obs_overhead`: the observability-plane cost canary,
    structurally validated (the measured <3% budget claim lives in
    PERF.md, from full-size storms on an idle box):
    - both phases produced real throughput and the 'on' phases PROVED
      the instrumented path ran (the owner completion counter covered
      every storm — the row can never measure a disabled plane);
    - the overhead number is well-formed and the plane cannot cost a
      structural multiple of throughput (CI boxes are too noisy to
      gate the 3% budget itself — an off-vs-off control shows ±4%
      phantom overhead at this storm size);
    - the serve-path half ran the same alternating A/B on the CB
      engine and its 'on' phases PROVED the ledger fired (every storm
      request landed an e2e histogram observation)."""
    from ray_tpu.scripts.perf import main

    results = main([
        "--config", "obs_overhead",
        "--obs-storm-n", "300",
        "--obs-rounds", "2",
        "--obs-serve-requests", "8",
        "--num-workers", "2",
    ])
    row = results["obs_overhead"]
    assert results["metrics_off"]["tasks_per_s"] > 0
    assert results["metrics_on"]["tasks_per_s"] > 0
    assert row["instrumented"] == 1.0
    assert -50.0 < row["overhead_pct"] < 50.0
    srow = results["serve_obs_overhead"]
    assert results["serve_obs_off"]["tokens_per_sec"] > 0
    assert results["serve_obs_on"]["tokens_per_sec"] > 0
    assert srow["instrumented"] == 1.0
    assert -50.0 < srow["overhead_pct"] < 50.0


def test_rllib_ppo_row():
    """`--config rllib_ppo`: the BASELINE-config-#3 acceptance row,
    structurally validated at a small fleet shape (throughput numbers
    live in PERF.md, measured at the full 8-runner shape):
    - both headline metrics present and positive (env-steps/s AND
      learner updates/s — the bench must measure the whole pipeline,
      not just sampling);
    - exactly-once accounting: every env step the training loop
      consumed is ledger-recorded exactly once (no lost or
      double-counted sample batches);
    - the async overlap actually ran (overlap mode on, ratio
      well-formed)."""
    from ray_tpu.scripts.perf import main

    results = main([
        "--config", "rllib_ppo",
        "--rllib-runners", "2",
        "--rllib-envs-per-runner", "4",
        "--rllib-rollout-len", "16",
        "--rllib-iters", "2",
    ])
    row = results["rllib_ppo"]
    assert row["env_steps_per_s"] > 0
    assert row["updates_per_s"] > 0
    assert row["accounting_exact"] == 1.0
    assert row["env_steps"] == row["ledger_env_steps"] > 0
    assert row["overlap"] == 1.0
    assert 0.0 <= row["overlap_ratio"] <= 1.0
    assert row["gang_devices"] >= 2.0


def test_dag_calls_row():
    """`--config dag_calls`: the compiled-DAG fast-plane acceptance
    row, structurally validated at a small call count (the >=5x
    headline lives in PERF.md, measured at the full 2000-call shape):
    - both planes measured head-to-head in one cluster;
    - the compiled plane actually beats the per-call actor plane (the
      entire point of compiling);
    - tensor-channel bandwidth rows present for BOTH paths (inline
      slot and store-object spill)."""
    from ray_tpu.scripts.perf import main

    results = main([
        "--config", "dag_calls",
        "--dag-calls-n", "300",
        "--dag-tensor-mb", "1.0",
        "--num-workers", "2",
    ])
    row = results["dag_calls"]
    assert row["actor_us_per_call"] > 0
    assert row["dag_us_per_call"] > 0
    assert row["dag_us_per_call"] < row["actor_us_per_call"]
    assert row["speedup"] == pytest.approx(
        row["actor_us_per_call"] / row["dag_us_per_call"], rel=1e-6
    )
    assert row["tensor_inline_mb_s"] > 0
    assert row["tensor_spill_mb_s"] > 0


def test_pin_cores_rejects_oversubscription():
    import os

    import pytest

    from ray_tpu.scripts.perf import apply_core_pinning

    have = len(os.sched_getaffinity(0))
    with pytest.raises(RuntimeError, match="needs"):
        apply_core_pinning(have + 1)


def test_storage_faults_row():
    """`--config storage_faults`: the chaos-matrix acceptance row,
    structurally validated at a small size (wall-clock numbers live in
    PERF.md):
    - the epoch completed with EXACT row accounting despite the seeded
      bit-flip + ENOSPC + EIO schedule on the spill plane;
    - the schedule actually fired (fault-counter evidence from the
      daemon's /metrics: integrity errors or spill I/O errors > 0 —
      a zero-fault run would prove nothing);
    - the replay seed is recorded in the row."""
    from ray_tpu.scripts.perf import main

    results = main([
        "--config", "storage_faults",
        "--storage-faults-rows", "800000",
        "--storage-faults-store-mb", "4",
        "--storage-faults-seed", "1313",
    ])
    row = results["storage_faults"]
    assert row["rows_exact"] == 1.0
    assert row["rows_per_s"] > 0
    assert row["store_ratio"] >= 1.5
    assert row["seed"] == 1313.0
    assert (row["integrity_errors"] + row["spill_io_errors"]
            + row["spill_disk_full"]) > 0, (
        "no faults fired — the chaos schedule never touched the run"
    )


def test_data_shuffle_integrity_modes():
    """`--shuffle-integrity both`: the integrity on/off comparison is
    structurally well-formed (the measured ≤5% spill-path overhead
    claim lives in PERF.md — CI boxes are too noisy to gate it):
    both rows complete exactly, and the knob provably reached the
    spill plane (both runs spill; the off run still completes)."""
    from ray_tpu.scripts.perf import main

    results = main([
        "--config", "data_shuffle",
        "--shuffle-rows", "800000",
        "--shuffle-store-mb", "4",
        "--shuffle-integrity", "both",
    ])
    on = results["data_shuffle"]
    off = results["data_shuffle_integrity_off"]
    assert on["rows_exact"] == 1.0 and off["rows_exact"] == 1.0
    assert on["spill_bytes"] > 0 and off["spill_bytes"] > 0
    assert on["integrity_on"] == 1.0 and off["integrity_on"] == 0.0
    assert "overhead_pct" in results["integrity_overhead"]
