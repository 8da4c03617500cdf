"""User-visible exception hierarchy.

Mirrors the surface of the reference's `python/ray/exceptions.py` so users
switching over find the same failure hierarchy: task errors wrap the user
traceback, worker/actor/node crashes and lost objects are distinct types,
and `get` re-raises the underlying cause.
"""

from __future__ import annotations


class RayTpuError(Exception):
    """Base class for all framework errors."""


class TaskError(RayTpuError):
    """A task raised an exception; re-raised at `get` with the remote
    traceback attached (reference: RayTaskError)."""

    def __init__(self, message: str, remote_traceback: str = "", cause_type: str = ""):
        super().__init__(message)
        self.remote_traceback = remote_traceback
        self.cause_type = cause_type

    def __str__(self):
        base = super().__str__()
        if self.remote_traceback:
            return f"{base}\n\n--- remote traceback ---\n{self.remote_traceback}"
        return base


class WorkerCrashedError(RayTpuError):
    """The worker process executing the task died (reference:
    WorkerCrashedError)."""


class ActorDiedError(RayTpuError):
    """The actor is dead and will not be restarted (reference:
    RayActorError / ActorDiedError)."""

    def __init__(self, message: str = "The actor died.", actor_id=None):
        super().__init__(message)
        self.actor_id = actor_id


class ActorUnavailableError(RayTpuError):
    """The actor is temporarily unreachable (e.g. restarting)."""


class ObjectLostError(RayTpuError):
    """An object was lost from the store and could not be reconstructed
    from lineage (reference: ObjectLostError)."""

    def __init__(self, message: str = "Object lost.", object_id=None):
        super().__init__(message)
        self.object_id = object_id


class ObjectReconstructionFailedError(ObjectLostError):
    """Lineage reconstruction was attempted but failed (max retries
    exceeded or lineage evicted)."""


class ObjectCorruptionError(ObjectLostError):
    """An object's bytes failed checksum verification — on restore
    from a spilled file or on node-to-node receive — and could not be
    re-fetched clean.  Subclasses `ObjectLostError` because the
    recovery path is the same: the corrupt copy is quarantined/dropped
    and the object re-derives via lineage where lineage is retained
    (`core/integrity.py`; corruption is treat-as-lost, never
    silently-wrong data)."""


class TaskCancelledError(RayTpuError):
    """The task was cancelled before completion (reference:
    TaskCancelledError; raised by `get` on a cancelled ref)."""

    def __init__(self, message: str = "Task was cancelled.", task_id=None):
        super().__init__(message)
        self.task_id = task_id


class GetTimeoutError(RayTpuError, TimeoutError):
    """`get(timeout=...)` expired.

    Carries the timeout that expired and (when known) the object id the
    caller was waiting on, so handlers can log/retry the specific ref
    instead of a bare "timed out" string.
    """

    def __init__(self, message: str = "", timeout_s=None, object_id=None):
        super().__init__(message)
        self.timeout_s = timeout_s
        self.object_id = object_id


class DeadlineExceededError(GetTimeoutError):
    """An end-to-end task deadline (`.options(timeout_s=...)`) expired:
    the caller has given up, so the runtime fails fast instead of
    re-queueing/retrying work nobody is waiting for (reference analog:
    gRPC deadline propagation).  Subclasses GetTimeoutError so existing
    `except GetTimeoutError` call sites keep working."""


class PrefixCacheUnsupportedError(RayTpuError, ValueError):
    """`prefix_cache=True` was asked of a serve engine whose model keeps
    its context as a per-slot STATE (`serve/engine_model.SlotState`): a
    radix trie shares a prefix by pointing block tables at the same
    blocks, and a state has no blocks to point at."""


class BackPressureError(RayTpuError):
    """The target's admission queue is full: the request was rejected
    IMMEDIATELY instead of queueing unboundedly (reference analog:
    serve's max_queued_requests rejection).  Carries `retry_after_s`,
    a hint for when capacity is expected to free — the HTTP proxy
    translates it to `503` + a `Retry-After` header, the gRPC proxy to
    `RESOURCE_EXHAUSTED` with `retry-after` trailing metadata.

    The hint is ALSO embedded in the message text: a rejection raised
    inside a replica crosses the wire as a `TaskError` (which keeps
    only the message + cause type), and `backpressure_retry_after`
    recovers the hint from either shape."""

    def __init__(self, message: str = "admission queue is full",
                 retry_after_s: float = 1.0):
        self.retry_after_s = max(0.0, float(retry_after_s))
        super().__init__(
            f"{message} [retry_after_s={self.retry_after_s:.3f}]"
        )


def backpressure_retry_after(err: BaseException):
    """The retry-after hint (seconds) if `err` is — or wraps, as a
    remote `TaskError` — a `BackPressureError`; None otherwise.  The
    single overload-classification chokepoint for the HTTP/gRPC
    proxies and any caller-side retry logic."""
    import re

    if isinstance(err, BackPressureError):
        return err.retry_after_s
    if (isinstance(err, TaskError)
            and err.cause_type == "BackPressureError"):
        m = re.search(r"\[retry_after_s=([0-9.]+)\]", str(err))
        try:
            return float(m.group(1)) if m else 1.0
        except ValueError:
            return 1.0
    return None


def is_deadline_expiry(err: BaseException) -> bool:
    """True for a deadline expiry in either shape: the typed
    `DeadlineExceededError` (router/owner-side) or its remote
    `TaskError` wrapping (a replica-side shed crossing the wire)."""
    if isinstance(err, DeadlineExceededError):
        return True
    return (isinstance(err, TaskError)
            and err.cause_type == "DeadlineExceededError")


class NodeDiedError(RayTpuError):
    """The node hosting the computation died."""


class RuntimeEnvSetupError(RayTpuError):
    """Preparing the task/actor runtime environment failed."""


class PlacementGroupUnschedulableError(RayTpuError):
    """The placement group cannot fit in the cluster."""


class OutOfMemoryError(RayTpuError):
    """Task killed by the memory monitor (reference: OomKillerError)."""


class PendingCallsLimitExceeded(RayTpuError):
    """Actor max_pending_calls exceeded."""
