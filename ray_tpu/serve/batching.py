"""Request batching for replicas.

Reference: `python/ray/serve/batching.py` (`@serve.batch`) — an async
decorator that queues individual calls and invokes the wrapped function
once per batch, unlocking MXU-friendly batched inference: on TPU the win
is larger than on GPU because XLA compiles per shape, so replicas batch
to a fixed `max_batch_size` and the compiled program is reused.
"""

from __future__ import annotations

import asyncio
import functools
from typing import Any, Callable, List, Optional

from ray_tpu.exceptions import BackPressureError


class _BatchQueue:
    def __init__(self, fn: Callable, max_batch_size: int,
                 batch_wait_timeout_s: float,
                 max_queued_requests: int = -1):
        self._fn = fn
        self._max = max_batch_size
        self._wait = batch_wait_timeout_s
        self._max_queued = max_queued_requests
        self._queue: asyncio.Queue = asyncio.Queue()
        self._task: Optional[asyncio.Task] = None
        self._executing = False  # a batch is inside the user function

    def _ensure_loop(self):
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(self._loop())

    async def submit(self, item: Any) -> Any:
        if (self._max_queued >= 0 and self._executing
                and self._queue.qsize() >= self._max_queued):
            # bounded like every other admission queue: a stalled (or
            # merely slow) batched function must surface as immediate
            # typed backpressure, not as an unbounded pending list
            # whose callers all eventually time out.  The cap applies
            # only while a batch is EXECUTING downstream — matching
            # the engine's max_queued semantics, where work that the
            # consumer will pick up immediately is not really waiting
            # (so max_queued=0 means "serve while the downstream keeps
            # up, never queue behind it", not "reject everything").
            # Hint: one batch wait — the soonest a batch can drain.
            raise BackPressureError(
                f"batch queue full (max_queued_requests="
                f"{self._max_queued})",
                retry_after_s=max(0.05, self._wait),
            )
        fut = asyncio.get_running_loop().create_future()
        self._queue.put_nowait((item, fut))
        self._ensure_loop()
        return await fut

    async def _gather_batch(self) -> List:
        batch = [await self._queue.get()]
        loop = asyncio.get_running_loop()
        deadline = loop.time() + self._wait
        while len(batch) < self._max:
            remaining = deadline - loop.time()
            if remaining <= 0:
                break
            try:
                batch.append(
                    await asyncio.wait_for(self._queue.get(), timeout=remaining)
                )
            except asyncio.TimeoutError:
                break
        return batch

    async def _loop(self):
        while True:
            batch = await self._gather_batch()
            items = [b[0] for b in batch]
            futs = [b[1] for b in batch]
            self._executing = True
            try:
                results = await self._fn(items)
                if results is None or len(results) != len(items):
                    raise RuntimeError(
                        "batched function must return one result per input "
                        f"(got {0 if results is None else len(results)} for "
                        f"{len(items)} inputs)"
                    )
                for fut, res in zip(futs, results):
                    if not fut.done():
                        fut.set_result(res)
            except BaseException as e:  # noqa: BLE001 — callers must
                # never hang: even cancellation resolves the in-flight
                # batch's futures before the loop task dies
                for fut in futs:
                    if not fut.done():
                        fut.set_exception(
                            e
                            if isinstance(e, Exception)
                            else RuntimeError(f"batch loop died: {e!r}")
                        )
                if not isinstance(e, Exception):
                    raise
            finally:
                self._executing = False


def batch(
    _fn: Optional[Callable] = None,
    *,
    max_batch_size: int = 10,
    batch_wait_timeout_s: float = 0.01,
    max_queued_requests: int = -1,
):
    """Decorator: turn `async def f(self, item)`-shaped handlers into
    batched `f(self, items: List)` execution (reference:
    `serve/batching.py` `@serve.batch`).

    `max_queued_requests` (default -1 = unbounded) bounds the pending
    list the same way the deployment-level admission cap does: the
    overflow submit raises `BackPressureError` (translated to 503 +
    Retry-After at the HTTP proxy) instead of queueing behind a
    stalled downstream forever."""

    def _decorate(fn: Callable):
        # one queue per bound instance (methods) or per function
        attr = f"__serve_batch_queue_{id(fn)}"

        @functools.wraps(fn)
        async def wrapper(*args):
            if len(args) == 2:  # bound method: (self, item)
                owner, item = args

                async def call(items):
                    return await fn(owner, items)

            elif len(args) == 1:  # plain function: (item,)
                owner, item = wrapper, args[0]

                async def call(items):
                    return await fn(items)

            else:
                raise TypeError(
                    "@serve.batch handlers take exactly one request argument"
                )
            q = getattr(owner, attr, None)
            if q is None:
                # per-instance overrides (reference:
                # set_max_batch_size/handle options): an owner may carry
                # `__serve_batch_overrides__ = {method_name: {...}}`
                over = getattr(owner, "__serve_batch_overrides__", {}).get(
                    getattr(fn, "__name__", ""), {}
                )
                q = _BatchQueue(
                    call,
                    over.get("max_batch_size", max_batch_size),
                    over.get("batch_wait_timeout_s", batch_wait_timeout_s),
                    over.get("max_queued_requests", max_queued_requests),
                )
                setattr(owner, attr, q)
            return await q.submit(item)

        wrapper._is_serve_batch = True
        return wrapper

    if _fn is not None:
        return _decorate(_fn)
    return _decorate
