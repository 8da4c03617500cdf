"""Fault-injection utilities for tests and chaos runs.

Reference: `python/ray/_private/test_utils.py` — `WorkerKillerActor`
(:1597), `RayletKiller` (:1536), `ResourceKillerActor` (:1433): actors
that kill cluster components on a cadence while a workload runs, the
substrate of the reference's chaos suites
(`release/nightly_tests/setup_chaos.py`).  Single-host clusters (the
`cluster_utils.Cluster` test shape) let killers deliver straight
SIGKILLs by pid.
"""

from __future__ import annotations

import os
import random
import signal
import time
from typing import List, Optional

import ray_tpu as rt


def list_workers() -> List[dict]:
    """All pool workers on the local node (id, pid, kind, idle)."""
    from ray_tpu.core.runtime import get_runtime

    return get_runtime().noded_call("list_workers") or []


def kill_random_worker(*, busy_only: bool = True,
                       exclude_actors: bool = True,
                       rng: Optional[random.Random] = None) -> Optional[int]:
    """SIGKILL one worker; returns the pid or None if no candidate.
    The runtime's worker-death path turns this into retriable task
    failures / actor restarts — the property chaos tests assert."""
    rng = rng or random
    candidates = [
        w for w in list_workers()
        if w["kind"] == "worker"
        and (not busy_only or not w["idle"])
        and (not exclude_actors or w["actor_id"] is None)
        and w["pid"] != os.getpid()
    ]
    if not candidates:
        return None
    victim = rng.choice(candidates)
    try:
        os.kill(victim["pid"], signal.SIGKILL)
    except ProcessLookupError:
        return None
    return victim["pid"]


@rt.remote(max_concurrency=2)  # stop() must interleave with run()
class WorkerKiller:
    """Resident killer: SIGKILLs a random busy task worker every
    `interval_s` until stopped (reference: WorkerKillerActor)."""

    def __init__(self, interval_s: float = 0.5, seed: int = 0):
        self.interval_s = interval_s
        self.rng = random.Random(seed)
        self.killed: List[int] = []
        self._stop = False

    def run(self, duration_s: float = 10.0) -> List[int]:
        deadline = time.time() + duration_s
        while not self._stop and time.time() < deadline:
            pid = kill_random_worker(rng=self.rng)
            if pid is not None:
                self.killed.append(pid)
            time.sleep(self.interval_s)
        return self.killed

    def stop(self) -> List[int]:
        self._stop = True
        return self.killed


# ----------------------------------------------------------------------
# environment capability probes (skip-guards for tier-1)
# ----------------------------------------------------------------------
_MULTIPROC_PROBE = r"""
import sys
import jax

rank, port = int(sys.argv[1]), sys.argv[2]
jax.distributed.initialize(
    f"127.0.0.1:{port}", num_processes=2, process_id=rank
)
import jax.numpy as jnp
from jax.experimental import multihost_utils

out = multihost_utils.process_allgather(jnp.ones((2,)) * (rank + 1))
assert float(out.sum()) == 6.0, out
"""

_multiproc_cpu_cache: Optional[tuple] = None


def jax_multiprocess_cpu_support() -> tuple:
    """(supported, reason): can this JAX/jaxlib run MULTI-PROCESS
    computations on the CPU backend (2 OS processes forming one global
    mesh via `jax.distributed`, the shape `test_train_distributed`
    miniaturizes)?  Some jaxlib builds compile the CPU client without
    cross-process collectives and fail any spanning computation with
    "Multiprocess computations aren't implemented on the CPU backend" —
    an environment limit, not a code path worth failing tier-1 over.
    Probes once per process with a real 2-process allgather."""
    global _multiproc_cpu_cache
    if _multiproc_cpu_cache is not None:
        return _multiproc_cpu_cache
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _MULTIPROC_PROBE, str(rank), str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for rank in (0, 1)
    ]
    ok, reason = True, ""
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            # one hung rendezvous means the pair is dead: kill BOTH
            # now so the second communicate() can't burn another 120 s
            for q in procs:
                q.kill()
            out, _ = p.communicate()
            ok, reason = False, "probe timed out (rendezvous hung)"
            continue
        if p.returncode != 0:
            ok = False
            if not reason:  # keep the FIRST cause: a later process
                # killed after a timeout would clobber it with SIGKILL
                tail = [ln for ln in (out or "").splitlines()
                        if ln.strip()]
                reason = (tail[-1][-200:] if tail
                          else f"exit {p.returncode}")
    _multiproc_cpu_cache = (ok, reason)
    return _multiproc_cpu_cache
