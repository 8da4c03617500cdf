"""The reduction from a trace to numbers, and the operation and byte
counts behind the roofline shares, against hand-worked values."""

import os
from types import SimpleNamespace as NS

import pytest

from benchmarks import manifest, roofline, trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tiny_v5e.xplane.pb")
MS = 1e6  # ns


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def profile(ops, modules=(), host=()):
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=list(ops)),
            NS(name="XLA Modules", events=list(modules))]),
        NS(name="/host:CPU", lines=[NS(name="loop", events=list(host))]),
        NS(name="/device:CUSTOM:Megascale Trace", lines=[]),
    ])


def test_busy_is_the_union_and_idle_is_the_rest():
    # a loop [0, 40) whose body ops [0,10) [10,30) nest under it, a gap,
    # [60, 80), a gap, [99, 100); the host's events run past both ends
    r = trace_reduce.reduce(profile(
        ops=[ev("%while.1", 0, 40), ev("%fusion.a", 0, 10),
             ev("%kernel", 10, 20), ev("%fusion.b", 60, 20),
             ev("%fusion.c", 99, 1)],
        host=[ev("step", -20, 65), ev("data", 45, 15), ev("other", 80, 50)]),
        annotations=("data", "step"), default_gap="rest")
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.100)   # device events only
    assert r["busy_s"] == pytest.approx(0.061)
    assert r["idle_share"] == pytest.approx(0.39)
    # self time: the loop is charged only what its body does not cover
    assert r["op_seconds"]["%while.1"] == pytest.approx(0.010)
    assert r["op_seconds"]["%kernel"] == pytest.approx(0.020)
    assert r["op_calls"]["%fusion.a"] == 1
    assert r["device_ops"][0][1] == pytest.approx(0.020)
    # gaps: [40, 60) is `step` to 45 and `data` from there; [80, 99)
    # has no annotation that was asked for
    gaps = dict(r["idle_gaps"])
    assert gaps["step"] == pytest.approx(0.005)
    assert gaps["data"] == pytest.approx(0.015)
    assert gaps["rest"] == pytest.approx(0.019)
    assert r["longest_gap_s"] == pytest.approx(0.020)


def test_a_gap_is_named_after_the_innermost_span_that_covers_it():
    """Two ticks of an engine's loop as its spans nest: the tick, inside
    it `admit` (inside that `plan` and `prefill`), `dispatch`, `harvest`
    (inside that `device_wait` and `harvest_host`), and the blocked
    `wait` between ticks.  The device idles [30, 52) and [60, 100)."""
    from benchmarks.planes.serve import ENGINE_SPANS

    host = [ev("engine.tick", 0, 50),
            ev("engine.admit", 30, 12), ev("engine.plan", 30, 4),
            ev("engine.prefill", 34, 8), ev("engine.dispatch", 43, 2),
            ev("engine.harvest", 45, 5), ev("engine.device_wait", 45, 3),
            ev("engine.harvest_host", 48, 2),
            ev("engine.wait", 50, 1),
            ev("engine.tick", 51, 60), ev("engine.admit", 51, 1),
            ev("engine.harvest", 60, 38), ev("engine.device_wait", 62, 30),
            ev("engine.harvest_host", 92, 6), ev("not.asked.for", 0, 200)]
    r = trace_reduce.reduce(profile(
        ops=[ev("%a", 0, 30), ev("%b", 52, 8), ev("%c", 100, 1)], host=host),
        annotations=ENGINE_SPANS, default_gap="rest")
    gaps = dict(r["idle_gaps"])
    want = {"engine.plan": 4, "engine.prefill": 8, "engine.admit": 1,
            # what a tick's phases leave of it: [42, 43) and [98, 100)
            "engine.tick": 1 + 2, "engine.dispatch": 2,
            "engine.device_wait": 3 + 30, "engine.harvest_host": 2 + 6,
            "engine.harvest": 2, "engine.wait": 1}
    assert gaps == pytest.approx({k: v * 1e-3 for k, v in want.items()})
    assert sum(gaps.values()) == pytest.approx(0.062)
    assert r["idle_gaps"][0][0] == "engine.device_wait"
    # the pieces are disjoint whatever the spans do: one that outlives
    # its parent owns the time past the parent's end, once
    assert trace_reduce.innermost([("p", 0, 10), ("c", 5, 15), ("q", 12, 20)]) \
        == [(0, 5, "p"), (5, 12, "c"), (12, 20, "q")]


def test_the_spans_asked_for_are_the_engines():
    import inspect

    from benchmarks.planes.serve import ENGINE_SPANS
    from ray_tpu.serve import llm_engine

    src = inspect.getsource(llm_engine)
    assert len(ENGINE_SPANS) == len(set(ENGINE_SPANS)) == 9
    for name in ENGINE_SPANS:
        tail = name.removeprefix("engine.")
        assert f'_span("{name}"' in src or f'_phase("{tail}"' in src, name


def test_union_merges_overlaps():
    merged, total = trace_reduce.union([(0, 5), (3, 8), (10, 12), (11, 11.5)])
    assert merged == [[0, 8], [10, 12]] and total == 10


def test_programs_containing_a_kernel():
    p = profile(
        ops=[ev("%k = bf16[4,2,8] custom-call(), custom_call_target=\"tpu_custom_call\"", 1, 2),
             ev("%k = bf16[4,2,8] custom-call(), custom_call_target=\"tpu_custom_call\"", 4, 2),
             ev("%fusion.9", 21, 3)],
        modules=[ev("jit__fn(1)", 0, 10), ev("jit__pf(2)", 20, 5)])
    got = trace_reduce.programs_containing(
        p, lambda n: "tpu_custom_call" in n and "bf16[4,2,8]" in n)
    assert got["calls"] == 1 and got["seconds"] == pytest.approx(0.010)
    assert got["op_calls"] == 2 and got["op_seconds"] == pytest.approx(0.004)


def test_an_empty_trace_reads_as_nothing():
    r = trace_reduce.reduce(NS(planes=[NS(name="/host:CPU", lines=[])]))
    assert r["devices"] == 0 and r["busy_s"] == 0.0


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded v5e trace in this checkout")
def test_recorded_v5e_trace_reduces():
    """A trace recorded on one v5e chip: a few jitted matmul steps with
    `TraceAnnotation("step")`s and sleeps between them."""
    r = trace_reduce.reduce_dir(FIXTURE, annotations=("step", "pause"),
                                default_gap="rest")
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0.0 < r["idle_share"] < 1.0
    assert any("fusion" in k or "dot" in k or "convolution" in k
               for k in r["op_seconds"])
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"], rel=0.05)
    assert any(k.startswith("jit_") for k in r["module_seconds"])
    assert r["idle_gaps"] and len(r["device_ops"]) <= 10


# ------------------------------------------------------------- roofline
PEAKS = manifest.peaks("TPU v5 lite")


def test_flash_counts_at_16x16x1024x64():
    fwd = roofline.flash_fwd(16, 16, 1024, 64)
    assert fwd == {"flops": 34_393_292_800, "bytes": 134_217_728}
    bwd = roofline.flash_bwd(16, 16, 1024, 64)
    assert bwd == {"flops": 85_983_232_000, "bytes": 268_435_456}
    full = roofline.flash_fwd(16, 16, 1024, 64, causal=False)
    assert full["flops"] == 4 * 16 * 16 * 1024 * 1024 * 64
    least = roofline.least_seconds(fwd, PEAKS)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(34_393_292_800 / 197e12)


def test_paged_decode_counts_at_64_slots_8_kv_heads_128():
    w = roofline.paged_decode(live_tokens=32768, batch=64, heads=32,
                              kv_heads=8, head_dim=128)
    assert w == {"flops": 536_870_912, "bytes": 134_217_728 + 1_048_576}
    least = roofline.least_seconds(w, PEAKS)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(135_266_304 / 819e9)
    assert roofline.share(w, 2 * least["seconds"], PEAKS) == pytest.approx(50.0)
    assert roofline.share(w, 0.0, PEAKS) is None


def test_train_flops_per_token_for_gpt2_medium():
    n = 12 * 24 * 1024 * 1024 + 50257 * 1024
    assert n == 353_453_056
    got = roofline.dense_train_flops_per_token(n, 24, 1024, 1024)
    assert got == 6 * n + 6 * 24 * 1024 * 1024
    assert roofline.dense_train_flops_per_token(
        n, 24, 1024, 1024, causal=False) == 6 * n + 12 * 24 * 1024 * 1024
