"""The engine-phase readers (PR 25) on hand-made contexts: the values
they compute from a replica's `request_ring` and from the named
programs of a reduced trace, and the `None` they give a program that
keeps no ring and names no program (the parent of that PR)."""

import pytest

from benchmarks import manifest


def rec(queue_s, first_token_s, decode_s, harvests, status="ok"):
    return {"seq": 0, "t_done": 0.0, "status": status, "queue_s": queue_s,
            "prefill_dispatch_s": 0.01, "first_token_s": first_token_s,
            "decode_s": decode_s, "harvests": harvests, "tokens_in": 128,
            "tokens_hit": 0, "tokens_out": 8 * harvests}


def ctx_with(rings, answers, traces=None, chunk=8):
    """`rings`: {rid: [records]}; `answers`: {rid: count at the client}."""
    traces = traces or {}
    return {
        "plane": "serve", "config": {"engine": {"chunk": chunk}},
        "client": {"per_replica": answers},
        "replicas": [{"rid": rid, "engine": {"request_ring": ring},
                      "trace": traces.get(rid, {})}
                     for rid, ring in rings.items()],
    }


def read(name, ctx):
    return manifest.layer_metric(name).read(ctx)


# 20 window requests on replica "7" behind 3 warm-up records, one shed
WARM = [rec(9.0, 9.5, 9.0, 2) for _ in range(3)]
WINDOW = [rec(0.010 * i, 0.010 * i + 1.0 + 0.1 * i, 0.8 * 3, 4)
          for i in range(1, 20)] + [rec(0.5, None, None, 0, "shed_expired")]
CTX = ctx_with({"7": WARM + WINDOW}, {"7": 20})


def test_queue_wait_is_the_p95_of_the_windows_ok_records():
    # 19 ok records, queue 10..190 ms: nearest rank ceil(.95*19) = 19th
    assert read("engine_queue_wait_p95_ms", CTX) == pytest.approx(190.0)


def test_admit_to_first_token_is_first_token_less_queue():
    # 1000 + 100 i ms, i = 1..19: the 10th of 19
    assert read("engine_admit_to_first_token_p50_ms", CTX) == \
        pytest.approx(2000.0)


@pytest.mark.parametrize("name", ["engine_inter_token_p50_ms",
                                  "engine_inter_token_p50_ms.chat"])
def test_inter_token_is_decode_over_the_later_chunks_tokens(name):
    # 2.4 s over (4 - 1) chunks of 8 tokens = 100 ms a token
    assert read(name, CTX) == pytest.approx(100.0)
    # a request fed by one chunk has no interval to divide
    one = ctx_with({"7": [rec(0.1, 0.2, 0.0, 1)]}, {"7": 1})
    assert read(name, one) is None


def test_records_pool_over_replicas_and_k_caps_at_the_ring():
    a = [rec(0.1, 1.0, 1.0, 2)] * 4
    b = [rec(0.3, 1.0, 1.0, 2)] * 4
    # the client counted more than the ring kept: all of the ring
    ctx = ctx_with({"1": a, "2": b}, {"1": 4, "2": 600})
    assert read("engine_queue_wait_p95_ms", ctx) == pytest.approx(300.0)
    # a replica the client never heard from contributes nothing
    ctx = ctx_with({"1": a, "2": b}, {"1": 4})
    assert read("engine_queue_wait_p95_ms", ctx) == pytest.approx(100.0)


@pytest.mark.parametrize("name", [
    "engine_queue_wait_p95_ms", "engine_admit_to_first_token_p50_ms",
    "engine_inter_token_p50_ms", "engine_inter_token_p50_ms.chat"])
def test_a_program_without_the_ring_reads_none(name):
    parent = {"plane": "serve", "config": {"engine": {"chunk": 8}},
              "client": {"per_replica": {"7": 20}},
              "replicas": [{"rid": "7", "engine": {"active": 0}}]}
    assert read(name, parent) is None
    assert read(name, {"plane": "train"}) is None


def trace(modules, window_s=3.0):
    return {"devices": 1, "window_s": window_s, "module_seconds": modules}


@pytest.mark.parametrize("name", ["prefill_device_share",
                                  "prefill_device_share.chat"])
def test_prefill_share_sums_the_named_programs(name):
    t1 = trace({"jit_decode_chunk_w64(1)": 2.0, "jit_prefill_b128(2)": 0.30,
                "jit_suffix_prefill_s32_p4(3)": 0.15,
                "jit_kv_write_t128_n8(4)": 0.15, "jit_argmax(5)": 0.01})
    t2 = trace({"jit_decode_chunk_w16(1)": 2.4, "jit_prefill_b64(2)": 0.3})
    ctx = ctx_with({"1": [], "2": []}, {}, {"1": t1, "2": t2})
    # (0.6 / 3 + 0.3 / 3) / 2
    assert read(name, ctx) == pytest.approx(15.0)
    # decode alone in the window: a share of nothing, not None
    quiet = ctx_with({"1": []}, {},
                     {"1": trace({"jit_decode_chunk_w16(1)": 2.9})})
    assert read(name, quiet) == 0.0


@pytest.mark.parametrize("name", ["prefill_device_share",
                                  "prefill_device_share.chat"])
def test_prefill_share_is_none_without_names_or_modules(name):
    unnamed = trace({"jit__fn(1)": 2.0, "jit__pf(2)": 0.5})
    assert read(name, ctx_with({"1": []}, {}, {"1": unnamed})) is None
    no_modules = {"devices": 1, "window_s": 3.0, "module_seconds": {}}
    assert read(name, ctx_with({"1": []}, {}, {"1": no_modules})) is None
    assert read(name, ctx_with({"1": []}, {})) is None  # untraced run
