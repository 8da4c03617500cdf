"""Process-spawn environment: who may see the chip, and where compiled
programs are cached.

- **The lease decides the platform.**  A chip belongs to one process
  at a time, so workers are spawned with it hidden
  (`JAX_PLATFORMS=cpu`); the daemon's `TPU` lease grant
  (`accelerators.chip_isolation_env` → `set_accel_env`) is the only
  thing that flips a worker to `tpu`, before it imports JAX.  A worker
  without a lease cannot take the chip from the one that holds it.
- **The compile cache is placed from outside.**  An inherited
  `JAX_COMPILATION_CACHE_DIR` passes through untouched; otherwise every
  daemon and worker of every session shares ONE fixed directory inside
  the checkout (the path is part of the cache key, so a directory that
  moves never hits).  Environment only: no code sets the cache path
  through `jax.config`.  JAX's own threshold (keep a program only if
  it took a second to compile) is lowered to zero the same way: a
  serve replica's set-up compiles 65 programs of which 60 take under a
  second each, and a decode program that compiles in 0.9 s was compiled
  again by every run while a slower one was loaded (PERF.md §6, PR 26).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from ray_tpu.core.accelerators import JAX_PLATFORMS_ENV

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_CACHE_MIN_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def infra_env(base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Env for spawning a node daemon (and, through it, everything the
    session runs): the caller's, with the compile cache defaulted and
    every program kept in it, however quickly it compiled."""
    env = dict(base if base is not None else os.environ)
    env.setdefault(COMPILE_CACHE_ENV, DEFAULT_COMPILE_CACHE_DIR)
    env.setdefault(COMPILE_CACHE_MIN_SECS_ENV, "0")
    return env


def adopt_sys_path(paths) -> bool:
    """Prepend the driver's sys.path entries (those that exist here and
    aren't present yet), preserving their order.  Shared by the
    spawn-env path (worker_main) and the KV retry path (runtime) so the
    adoption policy cannot diverge.  Returns True if anything was
    added."""
    import sys

    added = False
    for p in reversed(list(paths)):
        if p and p not in sys.path and os.path.isdir(p):
            sys.path.insert(0, p)
            added = True
    return added


def worker_env(base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Env for spawning a worker: the daemon's, with the chip hidden.
    Until the worker imports JAX the pin is only a variable, which is
    what lets a later chip grant replace it with `tpu`."""
    env = infra_env(base)
    env[JAX_PLATFORMS_ENV] = "cpu"
    return env
