"""Worker process entry point.

Spawned by the node daemon (reference: `WorkerPool::StartWorkerProcess`,
`src/ray/raylet/worker_pool.h`); hosts a Runtime in worker mode whose io
loop receives execute_task pushes and runs user code in executor
threads (reference: the worker exec loop, `core_worker.cc:2908` +
`_raylet.pyx task_execution_handler:2222`).
"""

from __future__ import annotations

import logging
import os
import signal
import sys
import threading
import time

import faulthandler

faulthandler.register(signal.SIGUSR1, all_threads=True)


def install_asyncio_dump(get_loop, sig=signal.SIGUSR2):
    """`kill -USR2 <pid>` prints every pending asyncio task's coroutine
    stack to stderr — the coroutine-level sibling of the USR1 thread
    dump (thread stacks show an idle io loop even when a hundred
    coroutines are parked on never-resolving futures; this shows WHERE
    they are parked).  Safe in the handler: it only schedules the dump
    onto the loop."""
    import asyncio

    def _chain(coro):
        """Follow the await chain to its suspension point — get_stack
        alone shows only the outermost frame, which for a deep await
        chain says nothing about what is actually being waited on."""
        out = []
        hops = 0
        while coro is not None and hops < 24:
            hops += 1
            fr = (getattr(coro, "cr_frame", None)
                  or getattr(coro, "gi_frame", None))
            if fr is not None:
                out.append(f"{fr.f_code.co_name}:{fr.f_lineno}")
            coro = (getattr(coro, "cr_await", None)
                    or getattr(coro, "gi_yieldfrom", None))
        return out

    def _dump():
        tasks = [t for t in asyncio.all_tasks() if not t.done()]
        print(f"--- asyncio dump: {len(tasks)} pending tasks ---",
              file=sys.stderr, flush=True)
        for t in tasks:
            print(f"task {t.get_name()} {' -> '.join(_chain(t.get_coro()))}",
                  file=sys.stderr)
        print("--- end asyncio dump ---", file=sys.stderr, flush=True)

    def _handler(signum, frame):
        loop = get_loop()
        if loop is not None and loop.is_running():
            loop.call_soon_threadsafe(_dump)

    signal.signal(sig, _handler)


def main():
    logging.basicConfig(
        level=os.environ.get("RT_LOG_LEVEL", "INFO").upper(),
        format="%(asctime)s worker %(levelname)s %(message)s",
    )
    # Import parity with the driver: functions pickled BY REFERENCE
    # (module-level defs in importable modules — e.g. a pytest-imported
    # test module) must resolve here too.  Single-host clusters share
    # the filesystem, so adopting the driver's sys.path additions is
    # exact; multi-host deployments ship code via runtime_env
    # working_dir instead (reference: the driver's code_search_path /
    # runtime_env py_modules mechanism).
    extra = os.environ.get("RT_DRIVER_SYS_PATH")
    if extra:
        import json as _json

        from ray_tpu.core.env_utils import adopt_sys_path

        adopt_sys_path(_json.loads(extra))
    # test hook: simulate the slow-boot regime (heavy imports, a
    # contended core) that the worker pool's starting-worker
    # accounting must tolerate without a spawn storm
    boot_delay = float(os.environ.get("RT_TEST_WORKER_BOOT_DELAY", "0"))
    if boot_delay > 0:
        time.sleep(boot_delay)
    node_socket = os.environ["RT_NODE_SOCKET"]
    host, port = os.environ["RT_CONTROLLER"].rsplit(":", 1)

    from ray_tpu.core.runtime import Runtime, set_runtime

    rt = Runtime("worker")
    # publish the runtime BEFORE registering with the daemon: a task can
    # be pushed the instant registration lands, and its user code may
    # call get_runtime() immediately
    set_runtime(rt)
    install_asyncio_dump(lambda: getattr(rt, "loop", None))
    # tee BEFORE registering: a task can land the instant registration
    # does, and its first prints must not bypass the stream (reference:
    # log_monitor.py tailing worker files); the tee passes through to
    # this worker's session-dir log file either way
    from ray_tpu.core.log_stream import install_worker_tee

    install_worker_tee()
    rt.start(node_socket, (host, int(port)),
             serve_dir=os.path.dirname(node_socket))

    # exit when the node daemon goes away (socket closes) or parent dies
    ppid = os.getppid()
    try:
        while True:
            time.sleep(0.5)
            if rt.noded is None or rt.noded.closed:
                break
            if os.getppid() != ppid:
                break
    except KeyboardInterrupt:
        pass
    rt.shutdown()
    sys.exit(0)


if __name__ == "__main__":
    main()
