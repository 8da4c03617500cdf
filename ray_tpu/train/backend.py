"""Backend hooks: framework-specific worker-group setup.

Reference: `train/backend.py:32` Backend(on_start/on_training_start/
on_shutdown) + per-framework configs (`train/torch/config.py:66`).
The JAX backend replaces torch.distributed rendezvous with either
host-level collective groups (default: rides the framework's own object
plane) or `jax.distributed.initialize` (multi-host SPMD, SURVEY §5.8).
"""

from __future__ import annotations

import logging
import socket
from dataclasses import dataclass, field
from typing import Dict, Optional

from ray_tpu.train.worker_group import WorkerGroup

logger = logging.getLogger(__name__)


@dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    """Subclass and override hooks; all run driver-side, issuing
    `worker_group.execute` RPCs for per-worker setup."""

    def on_start(self, worker_group: WorkerGroup, backend_config: BackendConfig):
        pass

    def on_training_start(
        self, worker_group: WorkerGroup, backend_config: BackendConfig
    ):
        pass

    def on_shutdown(self, worker_group: WorkerGroup, backend_config: BackendConfig):
        pass


# ---------------------------------------------------------------------
# JAX backend
# ---------------------------------------------------------------------
@dataclass
class JaxConfig(BackendConfig):
    """distributed_mode:
    - "collective": workers sync grads via host-level collective groups
      (`ray_tpu.parallel.collectives`); each worker runs its own local
      jax runtime over its visible chips.  Right for one-process-per-
      host-or-chip data parallelism.
    - "jax_distributed": `jax.distributed.initialize` on every worker —
      one global XLA runtime, `jax.devices()` spans all workers, pjit
      shards globally.  Right for multi-host SPMD over ICI/DCN.
    - "none": no cross-worker setup.
    """

    distributed_mode: str = "collective"
    platform: Optional[str] = None  # force JAX_PLATFORMS on workers
    env_vars: Dict[str, str] = field(default_factory=dict)
    collective_group_name: str = "train"

    @property
    def backend_cls(self):
        return JaxBackend


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _setup_worker_env(env_vars: Dict[str, str], platform: Optional[str]):
    """Runs first on every train worker.  The platform is whatever the
    worker's lease decided (`core/env_utils.py`: `cpu` without a `TPU`
    lease, `tpu` with one) unless `JaxConfig.platform` names another;
    it has to be in the environment before the worker imports JAX."""
    import os

    os.environ.update(env_vars)
    if platform:
        os.environ["JAX_PLATFORMS"] = platform


def _init_collective(world_size: int, rank: int, group_name: str):
    from ray_tpu.parallel import collectives

    collectives.init_collective_group(world_size, rank, group_name)


def _init_jax_distributed(coordinator: str, num_processes: int, process_id: int):
    import jax

    # (CPU multi-process collectives ride gloo, JAX's default wiring)
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


class JaxBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        worker_group.execute(
            _setup_worker_env, backend_config.env_vars, backend_config.platform
        )

    def on_training_start(
        self, worker_group: WorkerGroup, backend_config: JaxConfig
    ):
        n = len(worker_group)
        mode = backend_config.distributed_mode
        if n <= 1 or mode == "none":
            return
        if mode == "collective":
            import ray_tpu as rt

            # rank 0 first: it hosts the rendezvous actor others look up
            rt.get(worker_group.workers[0].execute.remote(
                _init_collective, n, 0, backend_config.collective_group_name
            ))
            rt.get([
                w.execute.remote(
                    _init_collective, n, i, backend_config.collective_group_name
                )
                for i, w in enumerate(worker_group.workers)
                if i > 0
            ])
        elif mode == "jax_distributed":
            # pick host AND port on worker 0 — the coordinator binds
            # there, so a driver-side free port would be wrong
            host, port = worker_group.execute_single(0, _coordinator_addr)
            coordinator = f"{host}:{port}"
            import ray_tpu as rt

            rt.get([
                w.execute.remote(_init_jax_distributed, coordinator, n, i)
                for i, w in enumerate(worker_group.workers)
            ])
        else:
            raise ValueError(f"unknown distributed_mode: {mode}")

    def on_shutdown(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        # Kill the rendezvous actor driver-side: worker 0 may already be
        # dead (FailureConfig restart path), and the named actor must not
        # survive into the next attempt or rank 0's re-registration
        # collides with the stale name.
        if len(worker_group) > 1 and backend_config.distributed_mode == "collective":
            import ray_tpu as rt

            name = f"__rt_collective__{backend_config.collective_group_name}"
            try:
                rt.kill(rt.get_actor(name))
            except Exception as e:
                # best-effort: the rendezvous actor may never have been
                # created (group died before on_training_start)
                logger.debug("rendezvous actor cleanup: %s", e)


def _coordinator_addr():
    host = socket.gethostbyname(socket.gethostname())
    return host, _free_port()
