"""From a profiler trace (`*.xplane.pb`, as `jax.profiler` writes it)
to the numbers the per-layer metrics read.  Part of the yardstick: the
reduction lives with the benchmark, so every PR computes the same
number the same way.

    python -m benchmarks.trace_reduce <dir-or-file> [--dump]

What a TPU trace holds (read by hand from a v5e trace before this was
written): one plane per chip, `/device:TPU:<n>`, with a line `XLA Ops`
(one event per executed HLO op, named as the HLO prints it:
`fusion.123`, `custom-call.7`, `while.2`, ...) and a line `XLA Modules`
(one event per executed program, named `jit_<fn>(<hash>)`); host
threads are lines of `/host:CPU`, where `TraceAnnotation`s appear under
their own names.  All planes share one clock (nanoseconds).

- busy: the union of the device's op intervals; idle share = 1 - busy
  over the traced window, which runs from the first to the last DEVICE
  event (the profiler attaches and detaches mid-flight, so the host's
  first and last events bracket time in which the device's work was
  simply not recorded).
- per-op sums: seconds and calls per op name, and per program.
- gaps: every idle interval, each part of it named after the INNERMOST
  of the host annotations asked for that covers that part (a tick's
  phase before the tick), what none covers after `default_gap`; summed
  by name, the largest first.
"""

from __future__ import annotations

import bisect
import glob
import os
import shutil
import sys
from collections import defaultdict

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def copy_xplane(src_dir: str, dst: str) -> None:
    os.makedirs(os.path.dirname(dst) or ".", exist_ok=True)
    shutil.copyfile(find_xplane(src_dir), dst)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(path))


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def _events(line):
    """(name, start_ns, end_ns) of a line's events."""
    for ev in line.events:
        s = float(ev.start_ns)
        yield ev.name, s, s + float(ev.duration_ns)


def union(intervals):
    """Merged, sorted intervals and their total length."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged, sum(e - s for s, e in merged)


def self_times(events):
    """(name, self_ns, start, end) per event of one line, where a
    parent (a `while`, a `call`) is charged only what its children do
    not cover: the ops line nests a loop's body under the loop."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][2] <= s:
            n, s0, e0, kids = stack.pop()
            out.append((n, (e0 - s0) - kids, s0, e0))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        n, s0, e0, kids = stack.pop()
        out.append((n, (e0 - s0) - kids, s0, e0))
    return out


def innermost(spans):
    """Disjoint, sorted (start, end, name) pieces of the time some span
    covers, each named after the span that began LAST among those that
    cover it: of nested spans, the innermost."""
    # stack of (name, end); the pieces so far end at `cur`
    out, stack, cur = [], [], float("-inf")

    def own(until):  # the top of the stack owns [cur, until)
        nonlocal cur
        if until > cur:
            out.append((cur, until, stack[-1][0]))
            cur = until

    for name, s, e in sorted(spans, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][1] <= s:
            own(stack[-1][1])
            stack.pop()
        if stack:
            own(s)
        else:
            cur = max(cur, s)
        stack.append((name, e))
    while stack:
        own(stack[-1][1])
        stack.pop()
    return out


def short(name: str, n: int = 160) -> str:
    """An op's event name is its whole HLO line; keep what identifies
    it: the result name, the result shape and the op kind."""
    name = " ".join(name.split())
    target = ""
    if "custom_call_target=" in name:
        target = " " + name[name.index("custom_call_target="):].split(",")[0]
    return name[:n] + ("..." if len(name) > n else "") + target


def reduce(profile, *, annotations=(), default_gap="unattributed",
           top=10) -> dict:
    """`profile`: anything with `.planes` -> `.lines` -> `.events`
    (`ProfileData`, or a test's stand-in)."""
    lo, hi = float("inf"), float("-inf")
    devices, host_spans = {}, []
    for plane in profile.planes:
        dev = is_device_plane(plane.name)
        ops, mods = [], []
        for line in plane.lines:
            evs = list(_events(line))
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                for _, s, e in evs:
                    lo, hi = min(lo, s), max(hi, e)
            if dev and line.name == OPS_LINE:
                ops = evs
            elif dev and line.name == MODULES_LINE:
                mods = evs
            elif not dev:
                host_spans += [(n, s, e) for n, s, e in evs
                               if n in annotations]
        if dev and (ops or mods):
            devices[plane.name] = (ops or mods, mods)
    if not devices or hi <= lo:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0}
    window = (hi - lo) * 1e-9
    per_dev_busy, op_s, op_n, mod_s, mod_n = [], defaultdict(float), \
        defaultdict(int), defaultdict(float), defaultdict(int)
    gaps = []
    for ops, mods in devices.values():
        merged, busy = union((s, e) for _, s, e in ops)
        per_dev_busy.append(busy * 1e-9)
        for n, self_ns, _, _ in self_times(ops):
            op_s[n] += self_ns * 1e-9
            op_n[n] += 1
        for n, s, e in mods:
            mod_s[n] += (e - s) * 1e-9
            mod_n[n] += 1
        edges = [[lo, lo]] + merged + [[hi, hi]]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((e0, s1))
    gap_by = defaultdict(float)
    pieces = innermost(host_spans)
    ends = [e for _, e, _ in pieces]
    for g0, g1 in gaps:
        named = 0.0
        for i in range(bisect.bisect_right(ends, g0), len(pieces)):
            s, e, n = pieces[i]
            if s >= g1:
                break
            gap_by[n] += (min(g1, e) - max(g0, s)) * 1e-9
            named += min(g1, e) - max(g0, s)
        if g1 - g0 > named:
            gap_by[default_gap] += (g1 - g0 - named) * 1e-9
    n_dev = len(devices)
    busy_s = sum(per_dev_busy) / n_dev
    rank = lambda d: [[short(k), v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": window, "busy_s": busy_s, "devices": n_dev,
        "idle_share": 1.0 - busy_s / window,
        "op_seconds": dict(op_s), "op_calls": dict(op_n),
        "module_seconds": dict(mod_s), "module_calls": dict(mod_n),
        "device_ops": rank({k: v / n_dev for k, v in op_s.items()}),
        "idle_gaps": rank({k: v / n_dev for k, v in gap_by.items()}),
        "longest_gap_s": max((b - a for a, b in gaps), default=0.0) * 1e-9,
    }


def programs_containing(profile, op_pred) -> dict:
    """Per device: executed programs (`XLA Modules` events) that hold at
    least one op `op_pred(name)` accepts -> {"seconds", "calls",
    "op_seconds", "op_calls"} summed over devices."""
    out = {"seconds": 0.0, "calls": 0, "op_seconds": 0.0, "op_calls": 0}
    for plane in profile.planes:
        if not is_device_plane(plane.name):
            continue
        lines = {l.name: l for l in plane.lines}
        if OPS_LINE not in lines or MODULES_LINE not in lines:
            continue
        hits = sorted((s, e) for n, s, e in _events(lines[OPS_LINE])
                      if op_pred(n))
        out["op_calls"] += len(hits)
        out["op_seconds"] += sum(e - s for s, e in hits) * 1e-9
        i = 0
        for _, s, e in sorted(_events(lines[MODULES_LINE]),
                              key=lambda t: t[1]):
            while i < len(hits) and hits[i][0] < s:
                i += 1
            if i < len(hits) and hits[i][0] < e:
                out["seconds"] += (e - s) * 1e-9
                out["calls"] += 1
    return out


def reduce_dir(path: str, kernels=None, **kw) -> dict:
    """`kernels`: {label: predicate over an op's name}; each adds
    `kernels[label]` = programs_containing(...) to the result."""
    prof = load(path)
    out = reduce(prof, **kw)
    out["kernels"] = {k: programs_containing(prof, pred)
                      for k, pred in (kernels or {}).items()}
    return out


def dump(path: str) -> None:
    prof = load(path)
    for plane in prof.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(_events(line))
            tot = defaultdict(float)
            for n, s, e in evs:
                tot[n] += (e - s) * 1e-9
            print(f"  line {line.name!r}: {len(evs)} events")
            for n, v in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                print(f"    {v:10.6f} s  {n}")


if __name__ == "__main__":
    if "--dump" in sys.argv:
        dump(sys.argv[1])
    else:
        import json

        r = reduce_dir(sys.argv[1])
        print(json.dumps({k: v for k, v in r.items()
                          if not k.endswith(("_seconds", "_calls"))}, indent=1))
