#!/usr/bin/env python3
"""benchmarks/run.py — the benchmark's command, and a process guard and
nothing else.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python benchmarks/run.py --workload <cell> --rehearse   # CPU, toy shapes, exits 3

It never imports JAX and never starts a runtime.  It checks that the
host shows the chips the cell asks for, makes itself the subreaper of
everything the run starts, runs the cell (`python -m benchmarks.cell`)
in a session of its own under a marker variable every descendant
inherits, relays the cell's lines while holding back the last one, and
— however the cell ends: exit, crash, the guard's own deadline,
SIGTERM, SIGINT — kills the session, sweeps `/proc` for anything in it
or carrying the marker, reaps until no child is left, removes the
run's temporary directory and shared-memory segments, and only then
prints the held-back line as its own last line.  If anything it started
is still alive after that, the run prints the survivors and
`"correct": false`, and exits non-zero.
"""

from __future__ import annotations

import argparse
import ctypes
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import uuid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARK_ENV = "RT_BENCH_RUN_MARK"
# a cell's first run in a checkout compiles: 1200 s are allowed
DEADLINE_S = float(os.environ.get("RT_BENCH_DEADLINE_S") or 1150.0)
REAP_BOUND_S = 30.0   # kill + sweep + reap; anything alive after this is reported
PR_SET_CHILD_SUBREAPER = 36


def log(msg: str) -> None:
    print(f"[bench.guard] {msg}", file=sys.stderr, flush=True)


def load_cell(workload: str) -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    sys.exit(f"bench: no workload {workload!r} in BENCHMARK.json")


def require_chips(want: int) -> None:
    """Fail, naming the missing device, unless this host shows `want`
    chips — learned from the device nodes, never from JAX."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in [
            p.strip() for p in platforms.lower().split(",")]:
        sys.exit(f"bench: no accelerator: JAX_PLATFORMS={platforms!r} hides "
                 "the TPU from this run; the benchmark does not run on the "
                 "CPU (see --rehearse)")
    sys.path.insert(0, REPO)
    from ray_tpu.core import accelerators

    have = accelerators.detect_num_chips()
    if have < want:
        sys.exit(f"bench: no accelerator: this host shows {have} TPU chip(s) "
                 f"(/dev/accel*, /dev/vfio/*), the cell needs {want}; the "
                 "benchmark does not run on the CPU (see --rehearse)")


def become_subreaper() -> bool:
    """Orphaned descendants re-parent to this process, so `waitpid` can
    see every one of them end."""
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        return libc.prctl(PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0) == 0
    except (OSError, AttributeError):
        return False


# ----------------------------------------------------------------------
# /proc sweep
# ----------------------------------------------------------------------
def _session_of(pid: int):
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            stat = f.read().decode(errors="replace")
    except OSError:
        return None, None
    # "pid (comm) state ppid pgrp session ..." — comm may hold spaces
    rest = stat[stat.rfind(")") + 2:].split()
    return rest[0], int(rest[3])  # state, session


def _has_mark(pid: int, mark: bytes) -> bool:
    try:
        with open(f"/proc/{pid}/environ", "rb") as f:
            return mark in f.read()
    except OSError:
        return False


def _cmdline(pid: int) -> str:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode(errors="replace").strip()
    except OSError:
        return ""


def survivors(session: int, mark: str) -> dict:
    """pid -> command line of every process in `session` or carrying
    the run's marker, zombies included (a zombie leader can still have
    live threads; `reap` collects it once it is really gone)."""
    needle = f"{MARK_ENV}={mark}".encode()
    me = os.getpid()
    found = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == me:
            continue
        pid = int(entry)
        state, sid = _session_of(pid)
        if state is None:
            continue
        if sid == session or _has_mark(pid, needle):
            found[pid] = _cmdline(pid)
    return found


EXITS = {}  # pid -> exit code of everything `reap` collected


def reap() -> bool:
    """Collect every child that has ended (direct, or re-parented to
    this subreaper).  True once NO child is left at all: a killed
    process whose main thread is gone but whose other threads are still
    dying shows as a zombie and cannot be collected yet, so `none
    ready` is not `none left`."""
    while True:
        try:
            pid, status = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return True
        if pid == 0:
            return False
        EXITS[pid] = os.waitstatus_to_exitcode(status)


def kill_everything(session: int, mark: str) -> dict:
    """SIGKILL the session's group, then whatever the sweep still
    finds, until `waitpid` says no child is left and two sweeps in a
    row are empty; bounded.  Returns what is still alive (empty =
    clean)."""
    deadline = time.monotonic() + REAP_BOUND_S
    try:
        os.killpg(session, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    empty = 0
    alive = {}
    while time.monotonic() < deadline:
        childless = reap()
        alive = survivors(session, mark)
        if not alive and childless:
            empty += 1
            if empty >= 2:
                return {}
        else:
            empty = 0
            for pid in alive:
                try:
                    os.kill(pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        time.sleep(0.05)
    if reap() and not alive:
        return {}
    return alive or {0: "a child that waitpid cannot collect yet"}


def remove_leftovers(tmp: str) -> None:
    """The run's temporary directory, and the shared-memory segment a
    SIGKILLed node daemon cannot unlink.  libtpu's lock file is never
    touched."""
    for sdir in glob.glob(os.path.join(tmp, "rt", "session_*")):
        for seg in glob.glob(f"/dev/shm/rt_{os.path.basename(sdir)}_*"):
            try:
                os.unlink(seg)
            except OSError:
                pass
    shutil.rmtree(tmp, ignore_errors=True)


# ----------------------------------------------------------------------
def main() -> int:
    t_start = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy shapes, control flow only: never prints "
                         "a result line, always exits non-zero")
    args, extra = ap.parse_known_args()  # the rest goes to the cell as is
    cell = load_cell(args.workload)
    if not args.rehearse:
        require_chips(int(cell["chips"]))
    else:
        sys.path.insert(0, REPO)
    from ray_tpu.core.env_utils import infra_env

    if not become_subreaper():
        log("could not become a subreaper: orphans are found by the /proc "
            "sweep alone")
    # unique to this run; a caller that wants to look for the run's
    # processes itself (the teardown test) names its own
    mark = os.environ.get(MARK_ENV) or uuid.uuid4().hex
    tmp = tempfile.mkdtemp(prefix="rtb_")
    env = infra_env()  # the compile cache: inherited, else <repo>/.jax_cache
    env.update({
        MARK_ENV: mark, "RT_BENCH_T0": repr(t_start), "PYTHONUNBUFFERED": "1", "RT_TPU_NO_METADATA": "1",
        "RT_TMPDIR": os.path.join(tmp, "rt"),
        "RT_BENCH_DIR": os.path.join(tmp, "bench"),
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("BENCH_RUN", None)  # the driver's own; no part of a run
    if args.trace:
        env["RT_ENGINE_TICK_RING"] = "8192"  # every tick of the window
    if args.rehearse:
        env.update(JAX_PLATFORMS="cpu")
    os.makedirs(env["RT_BENCH_DIR"])
    cmd = [sys.executable, "-m", "benchmarks.cell", "--workload",
           args.workload, "--seed", str(args.seed), "--trace",
           str(args.trace)]
    if args.seconds is not None:
        cmd += ["--seconds", str(args.seconds)]
    if args.rehearse:
        cmd.append("--rehearse")
    cmd += extra

    stop = threading.Event()
    why = {"signal": None}

    def on_signal(signum, _frame):
        why["signal"] = signum
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    child = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                             text=True, start_new_session=True)
    session = child.pid  # start_new_session: sid == pgid == the child's pid
    held = {"line": None}

    def relay():
        for line in child.stdout:
            if held["line"] is not None:
                sys.stdout.write(held["line"])
                sys.stdout.flush()
            held["line"] = line
        stop.set()

    reader = threading.Thread(target=relay, daemon=True)
    reader.start()
    t_end = time.monotonic() + DEADLINE_S
    while not stop.is_set() and time.monotonic() < t_end:
        stop.wait(0.2)
    timed_out = not stop.is_set()
    if timed_out:
        log(f"the cell is over the guard's deadline of {DEADLINE_S:.0f} s")
    if why["signal"] is not None:
        log(f"signal {why['signal']}: ending the run")

    alive = kill_everything(session, mark)
    reader.join(timeout=5)
    reap()
    # `reap` collects the cell too (it waits for ANY child), so its exit
    # code is there, unless Popen saw it first
    rc = EXITS.get(child.pid, child.returncode)
    if rc is None:
        rc = -signal.SIGKILL
    remove_leftovers(tmp)

    if alive:
        for pid, cmdline in sorted(alive.items()):
            print(f"[bench.guard] still alive after {REAP_BOUND_S:.0f} s: "
                  f"pid {pid}: {cmdline}", flush=True)
        print(json.dumps({"correct": False, "attempted": 0, "failed": 0,
                          "metrics": {}, "device": {},
                          "problem": "processes survived the run"}),
              flush=True)
        return 1
    if why["signal"] is not None or timed_out:
        if held["line"] is not None:  # cut short: never a last line
            sys.stderr.write(held["line"])
        return 124 if timed_out else 128 + int(why["signal"])
    if held["line"] is not None:
        sys.stdout.write(held["line"])
        sys.stdout.flush()
    if args.rehearse and rc == 3:
        log("rehearsal passed: control flow only, nothing ran on a chip")
    return rc


if __name__ == "__main__":
    sys.exit(main())
