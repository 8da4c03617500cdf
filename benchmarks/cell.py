"""One run of one cell: the guard's child (`python -m benchmarks.cell`).

Finds the cell's configuration, traffic mix and metric readers by the
names in `BENCHMARK.json`, runs the plane the configuration names,
prints every number `correct` compares beside its limit, and prints the
result as its last line.  This process never imports JAX: the chip
belongs to the worker that holds the lease.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys
import time
import traceback

from benchmarks import loadgen, manifest


def log(msg: str) -> None:
    print(f"[bench.cell] {msg}", file=sys.stderr, flush=True)


QUIET = False  # a rehearsal prints counts and checks, never a timing


def say(obj) -> None:
    if QUIET and obj.get("note"):
        obj = {k: v for k, v in obj.items()
               if k in ("note", "attempted", "failed", "cut_at_end", "steps",
                        "completed_in_window", "per_replica")}
        if obj["note"] == "end_to_end_all":
            return
    print(json.dumps(obj), flush=True)


def apply_rehearsal(cfg: dict, mix: dict) -> tuple:
    """Toy shapes for the CPU walk-through: each file carries its own."""
    def over(d):
        d = dict(d)
        for k, v in d.pop("rehearsal", {}).items():
            d[k] = {**d[k], **v} if isinstance(d.get(k), dict) else v
        return d
    return over(cfg), over(mix)


def end_to_end(ctx: dict, mix: dict) -> dict:
    """The benchmark's own host-clock numbers (never the program's)."""
    out = {"setup_s": ctx["setup_s"]}
    if ctx["plane"] == "serve":
        c = ctx["client"]
        out["serve_tokens_per_s"] = c["tokens_per_s"]
        if mix["kind"] == "open_loop":
            out["request_p95_ms"] = loadgen.percentile(c["latency_ms"], 95)
    else:
        t = ctx["train"]
        out["train_tokens_per_s"] = (
            t["steps"] * t["tokens_per_step"] / t["elapsed_s"])
    return out


def device_of(ctx: dict, trace: bool) -> tuple:
    parts = (ctx["replicas"] if ctx["plane"] == "serve" else [ctx["train"]])
    d0 = parts[0]["device"]
    dev = {"platform": d0["platform"], "kind": d0["kind"],
           "count": sum(int(p["device"]["count"]) for p in parts),
           "memory_peak_bytes": max(int(p["memory_peak_bytes"]) for p in parts)}
    traces = [p["trace"] for p in parts if p.get("trace", {}).get("devices")]
    if trace and traces:
        dev["busy_s"] = sum(t["busy_s"] for t in traces) / len(traces)
        dev["window_s"] = sum(t["window_s"] for t in traces) / len(traces)
    return dev, (traces[0] if traces else None)


def main() -> int:
    t_process_start = float(os.environ.get("RT_BENCH_T0") or time.time())
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None,
                    help="builder's tool: run the lower-precision control "
                         "in the program's place (must come out not correct)")
    ap.add_argument("--rate", type=float, default=None,
                    help="builder's tool: offer another rate (the sweep)")
    ap.add_argument("--mix-set", action="append", default=[],
                    help="builder's tool: key=json, overrides a key of "
                         "the traffic mix for this run")
    ap.add_argument("--sweep", default=None,
                    type=lambda v: [float(x) for x in v.split(",")],
                    help="builder's tool: offer these rates one after the "
                         "other before the run proper (open loop)")
    ap.add_argument("--detail", default=None,
                    help="builder's tool: also write the whole context here")
    args = ap.parse_args()
    cell = manifest.cell(args.workload)
    cfg, mix = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    if args.rehearse:
        global QUIET
        QUIET = True
        cfg, mix = apply_rehearsal(cfg, mix)
    if args.seconds is None:
        args.seconds = float(mix.get("rehearsal_seconds", 4.0)
                             if args.rehearse
                             else manifest.manifest()["run_seconds"])
    if args.rate is not None:
        mix = {**mix, "rate_per_s": args.rate}
    for item in args.mix_set:
        k, v = item.split("=", 1)
        mix = {**mix, k: json.loads(v)}
    plane = importlib.import_module("benchmarks.planes." + cfg["plane"])
    try:
        ctx = plane.run(cell, cfg, mix, args, t_process_start)
    except Exception:
        traceback.print_exc()
        _dump_logs()
        return 1
    if "jax" in sys.modules:
        log("the cell's driver process imported JAX")
        return 1
    ctx.update(cell=cell, config=cfg, traffic=mix)
    device, trace = device_of(ctx, bool(args.trace))
    if not args.rehearse:
        ctx["peaks"] = manifest.peaks(device["kind"])

    v = plane.verdict(ctx, cfg)
    e2e = end_to_end(ctx, mix)
    if ctx["plane"] == "serve":
        c = ctx["client"]
        say({"note": "client", "attempted": c["attempted"],
             "failed": c["failed"], "cut_at_end": c["cut_at_end"],
             "completed_in_window": c["completed_in_window"],
             "unanswered_at_window_end": c["unanswered_at_window_end"],
             # the reading a closed cell had until PR 49, beside the
             # metric in every run: a note, no metric
             "tokens_ended_in_window_per_s":
                 c["tokens_ended_in_window_per_s"],
             "latency_samples": len(c["latency_ms"]),
             "latency_p50_ms": loadgen.percentile(c["latency_ms"], 50),
             "latency_p95_ms": loadgen.percentile(c["latency_ms"], 95),
             "late_p95_ms": loadgen.percentile(c["late_ms"], 95),
             "per_replica": c["per_replica"],
             "setup": [r["timing"] for r in ctx["ready"]],
             "check": [r["check"] for r in ctx["replicas"]],
             "check_s": ctx["check_s"]})
        attempted, failed = c["attempted"], c["failed"]
    else:
        t = ctx["train"]
        say({"note": "train", "steps": t["steps"], "elapsed_s": t["elapsed_s"],
             "first_loss": t["losses"][0], "last_loss": t["losses"][-1],
             "memory_peak_bytes": t["memory_peak_bytes"],
             "memory_runtime_peak_bytes": t["memory_runtime_peak_bytes"],
             "memory_limit_bytes": t.get("memory_limit_bytes"),
             "setup": t["timing"], "check": t["check"]})
        attempted, failed = t["steps"], t["steps"] - ctx["reported_steps"]

    if args.trace:
        want = manifest.metrics_for(cell["name"], "per_layer")
        metrics = {}
        for entry in want:
            mod = manifest.layer_metric(entry["name"])
            value = mod.read(ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": mod.UNIT}
    else:
        want = manifest.metrics_for(cell["name"], "end_to_end")
        metrics = {e["name"]: {"value": e2e[e["name"]], "unit": e["unit"]}
                   for e in want}
        say({"note": "end_to_end_all", **e2e})
    result = {"correct": bool(v["correct"]), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace and trace:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result.update(checks_of(v))
    say_checks(v)
    if args.detail:
        os.makedirs(os.path.dirname(args.detail) or ".", exist_ok=True)
        with open(args.detail, "w") as f:
            json.dump({"result": result, "ctx": ctx, "e2e": e2e}, f)
    if args.rehearse:
        # never a result line from a run without a chip
        log("rehearsal: " + json.dumps({"correct": result["correct"],
                                        "metrics": sorted(metrics)}))
        return 3
    if device["platform"] != "tpu" or device["count"] != int(cell["chips"]):
        log(f"the workers reported {device}; the cell needs "
            f"{cell['chips']} TPU chip(s)")
        return 1
    if args.control:
        say({"note": "control", "control": args.control,
             "correct": result["correct"]})
        return 4  # a control run never ends in a result line
    say(result)
    return 0


def checks_of(v: dict) -> dict:
    """What `correct` compared, for the result's line: the rows that
    FAILED under `failed_checks` (empty when correct), so that a
    refusal's record says which row and by how much, and every row
    beside its limit under `checks`, the line's last key."""
    def num(x):  # the line is strict JSON: no Infinity, no NaN
        return x if math.isfinite(x) else None

    rows = [[name, num(value), num(limit)] for name, value, limit in v["rows"]]
    return {"failed_checks": [r for r, (_, value, limit) in zip(rows, v["rows"])
                              if not value <= limit],
            "checks": rows}


def say_checks(v: dict) -> None:
    """Each number compared beside its limit, as the run's last lines
    on standard error (the result's line has them under `checks`)."""
    for name, value, limit in v["rows"]:
        log(json.dumps({"check": name, "value": value, "limit": limit,
                        "ok": bool(value <= limit)}))


def _dump_logs(tail: int = 40, files: int = 8) -> None:
    import glob

    tmp = os.environ.get("RT_TMPDIR", "")
    paths = (glob.glob(os.path.join(tmp, "session_*", "noded.out"))
             + glob.glob(os.path.join(tmp, "session_*", "logs", "*"))
             + glob.glob(os.path.join(os.environ.get("RT_BENCH_DIR", ""),
                                      "error_*.json")))
    paths.sort(key=os.path.getmtime)
    for path in paths[-files:]:
        try:
            with open(path, errors="replace") as f:
                lines = f.readlines()[-tail:]
        except OSError:
            continue
        print(f"----- {path} (last {len(lines)} lines) -----\n"
              + "".join(lines), file=sys.stderr, flush=True)


if __name__ == "__main__":
    sys.exit(main())
