"""`hybrid_prefill_moe_routed_ms`: the reader of `trace["prefill_scopes"]`,
which `planes/serve_hybrid.py` has kept since the hybrid's cell."""

import pytest

from benchmarks import manifest

READER = manifest.layer_metric("hybrid_prefill_moe_routed_ms")


def _ctx(*prefill_scopes, plane="serve"):
    return {"plane": plane,
            "replicas": [{"trace": {"prefill_scopes": s}} if s is not None
                         else {} for s in prefill_scopes]}


def test_it_declares_what_a_manifest_entry_would_say():
    assert (READER.LAYER, READER.UNIT, READER.SOURCE, READER.MOVES) == (
        "models", "ms", "device_trace", "serve_tokens_per_s")


@pytest.mark.parametrize("ctx,want", [
    # 30 calls, 0.6 s under the scope: 20 ms a program
    (_ctx({"programs_s": 0.9, "program_calls": 30, "moe_routed": 0.6,
           "moe_router": 0.04}), 20.0),
    # summed over replicas before the division
    (_ctx({"programs_s": 0.9, "program_calls": 30, "moe_routed": 0.6},
          {"programs_s": 0.3, "program_calls": 10, "moe_routed": 0.1}), 17.5),
    # an untraced run, a trace without prefill programs, a program
    # without the scope, another plane: nothing to read, and no raise
    (_ctx(None), None),
    (_ctx({"programs_s": 0.0, "program_calls": 0}), None),
    (_ctx({"programs_s": 0.9, "program_calls": 30, "gqa_attn": 0.2}), None),
    (_ctx({"programs_s": 0.9, "program_calls": 30, "moe_routed": 0.6},
          plane="train"), None),
], ids=["one-replica", "two-replicas", "untraced", "no-prefill-program",
        "no-such-scope", "train-plane"])
def test_it_reads_device_time_a_prefill_program(ctx, want):
    got = READER.read(ctx)
    assert got is None if want is None else got == pytest.approx(want)
