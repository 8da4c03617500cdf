"""Helpers the readers share.  A reader is `read(ctx) -> float | None`;
`ctx` holds the cell's own files (`cell`, `config`, `traffic`), the
client's reduced records (`client`), one entry per replica with the
program's counters and the reduced trace (`replicas`), the train
loop's spans (`train`), and the chip's published `peaks`."""

from statistics import median


def traces(ctx):
    parts = ctx.get("replicas") or ([ctx["train"]] if "train" in ctx else [])
    return [p["trace"] for p in parts if p.get("trace", {}).get("devices")]


def mean(xs):
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def med(xs):
    xs = list(xs)
    return median(xs) if xs else None


def kernel(ctx, name):
    """Summed over the traced chips: {"seconds", "calls"} of the
    programs that hold kernel `name`, {"op_seconds", "op_calls"} of the
    kernel itself; None where the trace has no such call."""
    found = [t["kernels"][name] for t in traces(ctx)
             if t.get("kernels", {}).get(name, {}).get("op_calls")]
    if not found:
        return None
    return {k: sum(f[k] for f in found) for k in found[0]}
