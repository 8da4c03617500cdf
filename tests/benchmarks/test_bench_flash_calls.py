"""`flash_fwd_calls_per_bwd`: how often the flash forward kernel runs
for one run of the backward kernel, from the calls a train trace
counts (2.0 under a bare remat, 1.0 where the block keeps the
kernel's results)."""

from types import SimpleNamespace as NS

import pytest

from benchmarks import manifest, trace_reduce
from benchmarks.planes import train as train_plane

READER = manifest.layer_metric("flash_fwd_calls_per_bwd")


def _kernel(calls, seconds_a_call):
    return {"seconds": 0.4 * calls, "calls": calls // 24 or 1,
            "op_seconds": seconds_a_call * calls, "op_calls": calls}


def _train_ctx(fwd_calls, bwd_calls):
    kernels = {}
    if fwd_calls:
        kernels["flash_fwd"] = _kernel(fwd_calls, 0.647e-3)
    if bwd_calls:
        kernels["flash_bwd"] = _kernel(bwd_calls, 1.42e-3)
    return {"plane": "train",
            "train": {"trace": {"devices": 1, "kernels": kernels}}}


SERVE_CTX = {"plane": "serve", "replicas": [
    {"trace": {"devices": 1, "kernels": {
        "flash_fwd": _kernel(48, 0.647e-3),
        "flash_bwd": _kernel(24, 1.42e-3)}}}]}


@pytest.mark.parametrize("ctx,want", [
    (_train_ctx(48, 24), 2.0),     # the replay runs the kernel again
    (_train_ctx(24, 24), 1.0),     # the replay reads what was kept
    (_train_ctx(168, 168), 1.0),   # a trace of seven steps
    (SERVE_CTX, None),             # off the train plane
    (_train_ctx(0, 24), None),     # the trace has no such kernel
    (_train_ctx(48, 0), None),
    ({"plane": "train", "train": {}}, None),  # an untraced run
], ids=["bare-remat", "kept", "seven-steps", "serve", "no-fwd", "no-bwd",
        "untraced"])
def test_the_reader_on_a_recorded_context(ctx, want):
    assert READER.read(ctx) == want


def check_the_manifest_finds_every_new_file():
    entry = next(p for p in manifest.manifest()["per_layer"]
                 if p["name"] == "flash_fwd_calls_per_bwd")
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) \
        == (READER.LAYER, READER.UNIT, READER.SOURCE, READER.MOVES)
    assert entry["better"] == "lower" and "workloads" not in entry
    assert "flash_fwd_calls_per_bwd" in [
        p["name"] for p in manifest.metrics_for("gpt2m_train_stream",
                                                "per_layer")]


def test_the_reader_declares_what_the_manifest_says():
    check_the_manifest_finds_every_new_file()


@pytest.mark.parametrize("fwd_a_layer", [2, 1], ids=["bare-remat", "kept"])
def test_from_a_trace_through_the_cells_own_predicates(fwd_a_layer):
    """Ops printed as the chip prints them (an HLO line each), counted
    by `planes/train.kernel_predicates` as a traced run counts them."""
    cfg = manifest.config("gpt2-medium")
    mix = manifest.traffic("train_stream")
    fwd = ('%flash_fwd.{} = (bf16[256,1024,64]{{2,1,0:T(8,128)(2,1)}}, '
           'f32[256,1024,1]{{2,1,0:T(8,128)}}) custom-call(%a, %b, %c), '
           'custom_call_target="tpu_custom_call"')
    bwd = ('%flash_bwd_fused.9 = (bf16[256,1024,64]{2,1,0}, '
           'bf16[256,1024,64]{2,1,0}, bf16[256,1024,64]{2,1,0}) '
           'custom-call(%q, %k, %v), custom_call_target="tpu_custom_call"')
    ns = lambda n, s, d: NS(name=n, start_ns=s * 1e6, duration_ns=d * 1e6)  # noqa: E731
    ops, t = [], 0.0
    for layer in range(24):            # the forward scan
        ops.append(ns(fwd.format(16), t, 0.6))
        t += 1.0
    for layer in range(24):            # the backward scan
        if fwd_a_layer == 2:
            ops.append(ns(fwd.format(15), t, 0.6))
        ops.append(ns(bwd, t + 1.0, 1.4))
        t += 3.0
    prof = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops),
        NS(name="XLA Modules", events=[ns("jit_step", 0.0, t)])])])
    kernels = {k: trace_reduce.programs_containing(prof, pred) for k, pred
               in train_plane.kernel_predicates(cfg, mix).items()}
    ctx = {"plane": "train",
           "train": {"trace": {"devices": 1, "kernels": kernels}}}
    assert kernels["flash_bwd"]["op_calls"] == 24
    assert READER.read(ctx) == float(fwd_a_layer)
