"""What both planes do inside the process that holds the chip."""

from __future__ import annotations

import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def count_compiles() -> list:
    """Registers a `jax.monitoring` listener; the returned list grows
    by (wall time, seconds) for every backend compilation — a program
    loaded from the persistent cache counts too."""
    import jax.monitoring

    compiles = []

    def on_event(name, secs, **_kw):
        if name == COMPILE_EVENT:
            compiles.append((time.time(), float(secs)))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return compiles


def memory_stats() -> dict:
    """The first local device's allocator statistics ({} where the
    backend keeps none, as the CPU's)."""
    import jax

    try:
        return jax.local_devices()[0].memory_stats() or {}
    except (RuntimeError, NotImplementedError, AttributeError):
        return {}
