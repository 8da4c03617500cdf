"""`ep16_prefill_moe_routed_ms`: the held share's `moe_routed` time a
prefill program, out of `trace["prefill_scopes"]` as
`planes/serve_window_full.py` keeps it."""

import pytest

from benchmarks import manifest

NAME = "ep16_prefill_moe_routed_ms"
READER = manifest.layer_metric(NAME)


def _ctx(*prefill_scopes, plane="serve"):
    return {"plane": plane,
            "replicas": [{"trace": {"prefill_scopes": s}} if s is not None
                         else {} for s in prefill_scopes]}


def check_the_manifest_finds_every_new_file():
    """The entry as PR 52 wrote it down and PR 54 appended it, held by
    name: listed once, for the one cell whose plane keeps the scope."""
    assert (READER.LAYER, READER.UNIT, READER.SOURCE, READER.MOVES) == (
        "models", "ms", "device_trace", "serve_tokens_per_s")
    want = {"name": NAME, "unit": READER.UNIT, "better": "lower",
            "source": READER.SOURCE, "layer": READER.LAYER,
            "moves": READER.MOVES, "workloads": ["mimo25_mixed_closed_8k"]}
    listed = [p for p in manifest.manifest()["per_layer"]
              if p["name"] == NAME]
    assert listed == [want]
    assert NAME in [p["name"] for p in manifest.metrics_for(
        "mimo25_mixed_closed_8k", "per_layer")]
    # the scope it reads is one the cell's plane keeps
    from benchmarks.planes import serve_window_full

    assert "moe_routed" in serve_window_full.SCOPES


def test_it_declares_what_a_manifest_entry_would_say():
    check_the_manifest_finds_every_new_file()


@pytest.mark.parametrize("ctx,want", [
    # 42 programs of both families, 0.89 s under the scope: 21.2 ms
    (_ctx({"programs_s": 2.9, "program_calls": 42, "moe_routed": 0.8904,
           "moe_router": 0.11, "full_attn": 0.84}), 21.2),
    # summed over replicas before the division
    (_ctx({"programs_s": 0.9, "program_calls": 30, "moe_routed": 0.6},
          {"programs_s": 0.3, "program_calls": 10, "moe_routed": 0.1}), 17.5),
    # an untraced run, a trace without prefill programs, a program
    # without the scope (the parent of the PR that scoped it), another
    # plane: nothing to read, and no raise
    (_ctx(None), None),
    (_ctx({"programs_s": 0.0, "program_calls": 0}), None),
    (_ctx({"programs_s": 0.9, "program_calls": 30, "full_attn": 0.2}), None),
    (_ctx({"programs_s": 0.9, "program_calls": 30, "moe_routed": 0.6},
          plane="train"), None),
], ids=["one-replica", "two-replicas", "untraced", "no-prefill-program",
        "no-such-scope", "train-plane"])
def test_it_reads_device_time_a_prefill_program(ctx, want):
    got = READER.read(ctx)
    assert got is None if want is None else got == pytest.approx(want)
