"""Device time by `jax.named_scope`, out of a profiler trace.

`jax.profiler.ProfileData` gives an op event its name (the HLO line)
and its own stats, but not its METADATA's stats, and the name stack an
op was traced under (`jit(decode_chunk_w64)/.../moe_routed/...`) is one
of those: the stat `tf_op` of the event's `XEventMetadata`.  So this
reads the `.xplane.pb` itself: a minimal reader of the protobuf wire
format and of the handful of `xplane.proto` fields needed (tsl/profiler/
protobuf/xplane.proto; field numbers below), no dependency.

    scope_seconds(path, scopes, program_prefixes) ->
        {"programs_s", "program_calls", <scope>: seconds, ...}

summed over the device planes: for every executed program whose name
starts with one of `program_prefixes`, the SELF time of the ops inside
it (a `while` is charged only what its body's ops do not cover) whose
name stack has `<scope>` as one of its parts.  A fusion carries the name stack of
its root op, so an op fused into a neighbour's fusion counts with the
neighbour.  A trace of a program without such scopes, or without the
stat, gives the program totals and no scope: readers then return None.
"""

from __future__ import annotations

from collections import defaultdict

from benchmarks import trace_reduce


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, a
    memoryview for length-delimited fields; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf):
    """XPlane: name=2, lines=3, event_metadata=4, stat_metadata=5."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, m = _map_entry(v)
            event_meta[k] = m
        elif f == 5:
            k, m = _map_entry(v)
            # XStatMetadata: id=1, name=2
            stat_names[k] = next(
                (_text(x) for g, x in _fields(m) if g == 2), "")
    return name, lines, event_meta, stat_names


def _event_metadata(buf, stat_names):
    """XEventMetadata: name=2, stats=5 -> (name, tf_op or "")."""
    name, tf_op = "", ""
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 5:
            # XStat: metadata_id=1, str_value=5, ref_value=7
            sid = text = ref = None
            for g, x in _fields(v):
                if g == 1:
                    sid = x
                elif g == 5:
                    text = _text(x)
                elif g == 7:
                    ref = x
            if stat_names.get(sid) == "tf_op":
                tf_op = text if text is not None else stat_names.get(ref, "")
    return name, tf_op


def _line(buf):
    """XLine: name=2, timestamp_ns=3, events=4 -> (name, [(metadata_id,
    start_ps, end_ps)]).  XEvent: metadata_id=1, offset_ps=2,
    duration_ps=3."""
    name, t0_ns, raw = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0_ns = v
        elif f == 4:
            raw.append(v)
    events = []
    for ev in raw:
        mid = off = dur = 0
        for g, x in _fields(ev):
            if g == 1:
                mid = x
            elif g == 2:
                off = x
            elif g == 3:
                dur = x
        start = t0_ns * 1000 + off
        events.append((mid, start, start + dur))
    return name, events


def scope_seconds(path: str, scopes, program_prefixes) -> dict:
    with open(trace_reduce.find_xplane(path), "rb") as f:
        space = memoryview(f.read())
    out = defaultdict(float)
    out["programs_s"], out["program_calls"] = 0.0, 0
    for f, plane in _fields(space):          # XSpace: planes=1
        if f != 1:
            continue
        name, lines, event_meta, stat_names = _plane(plane)
        if not trace_reduce.is_device_plane(name):
            continue
        by_name = dict(_line(l) for l in lines)
        ops = by_name.get(trace_reduce.OPS_LINE)
        mods = by_name.get(trace_reduce.MODULES_LINE)
        if not ops or not mods:
            continue
        meta = {}

        def of(mid):
            if mid not in meta:
                meta[mid] = _event_metadata(event_meta.get(mid, b""),
                                            stat_names)
            return meta[mid]

        programs = sorted((s, e) for mid, s, e in mods
                          if of(mid)[0].startswith(tuple(program_prefixes)))
        out["programs_s"] += sum(e - s for s, e in programs) * 1e-12
        out["program_calls"] += len(programs)

        def scope_of(mid):
            stack = of(mid)[1].split("/")  # "jit(f)/.../scope/.../op:"
            return next((s for s in scopes if s in stack), "")

        i = 0
        inside = []
        for mid, s, e in sorted(ops, key=lambda t: t[1]):
            while i < len(programs) and programs[i][1] <= s:
                i += 1
            if i < len(programs) and programs[i][0] <= s:
                inside.append((scope_of(mid), s, e))
        for scope, self_ps, _, _ in trace_reduce.self_times(inside):
            if scope:
                out[scope] += self_ps * 1e-12
    return dict(out)
