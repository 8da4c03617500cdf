"""Learner: the compiled training side of the RL stack.

Reference: `rllib/core/learner/learner.py:117` (`compute_gradients:449`,
`apply_gradients:592`, `update_from_batch:954`) and `learner_group.py:80`.

TPU-native inversion: where the reference scales learners with torch DDP
across actors, the primary scaling path here is SPMD *inside* one
compiled update — minibatches are sharded over a `jax.sharding.Mesh`
data axis and XLA inserts the gradient psums on ICI.  A multi-actor
mode (`num_learners > 1`) with host-collective gradient allreduce keeps
the reference's process-parallel shape available for CPU fleets.

The LEARNER GANG (`gang_devices >= 2`, or an explicit mesh): the PPO
update is one pjit'd program over a data-sharded mesh — every gang
member (mesh device) sees 1/N of each minibatch and XLA inserts the
gradient psum, so adding devices widens the update without touching the
training loop.  `update_minibatch_device` keeps metrics on device
(no host sync per minibatch) — the driver thread returns to collecting
sample envelopes while XLA executes, which is what hides sampling
wall-time behind the update (the async overlap the bench measures).
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import ray_tpu as rt
from ray_tpu.rllib.core.rl_module import RLModule, params_to_numpy

logger = logging.getLogger(__name__)


def make_data_mesh(num_devices: int):
    """A 1-D `jax.sharding.Mesh` over the first `num_devices` local
    devices with axis name "data" — the learner gang's substrate.  On
    CPU boxes, virtual devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set BEFORE
    jax initializes; `rllib/bench.py` and tests/conftest.py both do)."""
    import jax

    devices = jax.devices()
    if num_devices > len(devices):
        raise ValueError(
            f"gang of {num_devices} learner devices requested but only "
            f"{len(devices)} visible — on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_devices} "
            "before jax initializes"
        )
    from jax.sharding import Mesh

    return Mesh(np.array(devices[:num_devices]).reshape(num_devices),
                ("data",))


class Learner:
    """Owns params + optimizer state; update_minibatch is jitted once
    (static minibatch shapes) and reused every epoch."""

    def __init__(self, module: RLModule, loss_fn: Callable,
                 lr: float = 3e-4, grad_clip: Optional[float] = 0.5,
                 seed: int = 0, mesh: Any = None):
        import jax
        import optax

        self.module = module
        self._loss_fn = loss_fn
        self._mesh = mesh
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(grad_clip) if grad_clip else optax.identity(),
            optax.adam(lr),
        )
        self.params = module.init_params(jax.random.PRNGKey(seed))
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # replicate params across the data axis; XLA will psum grads
            repl = NamedSharding(mesh, P())
            self.params = jax.tree.map(
                lambda x: jax.device_put(x, repl), self.params
            )
        self.opt_state = self.optimizer.init(self.params)
        self._update = self._build_update()

    def _build_update(self):
        import jax

        import optax

        def update(params, opt_state, batch):
            def loss_wrap(p):
                return self._loss_fn(self.module, p, batch)

            (loss, metrics), grads = jax.value_and_grad(
                loss_wrap, has_aux=True
            )(params)
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            metrics = dict(metrics)
            metrics["total_loss"] = loss
            return params, opt_state, metrics

        jitted = jax.jit(update, donate_argnums=(0, 1))
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            data_sh = NamedSharding(self._mesh, P("data"))

            def sharded_update(params, opt_state, batch):
                batch = {
                    k: jax.device_put(v, data_sh) for k, v in batch.items()
                }
                return jitted(params, opt_state, batch)

            return sharded_update
        return jitted

    def update_minibatch(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        self.params, self.opt_state, metrics = self._update(
            self.params, self.opt_state, batch
        )
        return {k: float(v) for k, v in metrics.items()}

    def update_minibatch_device(self, batch: Dict[str, np.ndarray]
                                ) -> Dict[str, Any]:
        """One update WITHOUT the host sync: metrics stay device arrays
        (jax dispatch is async — the caller overlaps the XLA execution
        with its own work and floats the metrics once per iteration)."""
        self.params, self.opt_state, metrics = self._update(
            self.params, self.opt_state, batch
        )
        return metrics

    def get_weights_numpy(self):
        return params_to_numpy(self.params)

    def get_state(self) -> Dict[str, Any]:
        return {
            "params": params_to_numpy(self.params),
            "opt_state": params_to_numpy(self.opt_state),
        }

    def set_state(self, state: Dict[str, Any]):
        import jax.numpy as jnp
        import jax

        self.params = jax.tree.map(jnp.asarray, state["params"])
        self.opt_state = jax.tree.map(
            jnp.asarray, state["opt_state"],
            is_leaf=lambda x: isinstance(x, np.ndarray),
        )


class _RemoteLearner:
    """Actor wrapper: one DDP rank (reference: LearnerGroup's remote
    learner actors).  Gradient sync = host-collective allreduce over the
    flattened gradient vector."""

    def __init__(self, module: RLModule, loss_fn: Callable, lr: float,
                 grad_clip: Optional[float], seed: int, world_size: int,
                 rank: int, group_name: str):
        # the platform is the worker's lease's business
        # (`core/env_utils.py`): CPU unless this actor was given a chip
        from ray_tpu.parallel import collectives

        self._learner = Learner(module, loss_fn, lr, grad_clip, seed=seed)
        self._world = world_size
        self._rank = rank
        self._group = collectives.init_collective_group(
            world_size, rank, group_name
        )
        self._grad_update = self._build_ddp_update()

    def _build_ddp_update(self):
        import jax
        from jax import flatten_util  # noqa: F401 — registers jax.flatten_util

        learner = self._learner

        @jax.jit
        def grads_of(params, batch):
            def loss_wrap(p):
                return learner._loss_fn(learner.module, p, batch)

            (loss, metrics), grads = jax.value_and_grad(
                loss_wrap, has_aux=True
            )(params)
            metrics = dict(metrics)
            metrics["total_loss"] = loss
            flat, _ = jax.flatten_util.ravel_pytree(grads)
            return flat, metrics

        import optax

        @jax.jit
        def apply_flat(params, opt_state, flat):
            _, unravel = jax.flatten_util.ravel_pytree(params)
            grads = unravel(flat)
            updates, opt_state = learner.optimizer.update(
                grads, opt_state, params
            )
            params = optax.apply_updates(params, updates)
            return params, opt_state

        def update(batch):
            flat, metrics = grads_of(learner.params, batch)
            mean = self._group.allreduce(np.asarray(flat), op="mean")
            learner.params, learner.opt_state = apply_flat(
                learner.params, learner.opt_state, mean
            )
            return {k: float(v) for k, v in metrics.items()}

        return update

    def update_minibatch(self, batch) -> Dict[str, float]:
        return self._grad_update(batch)

    def get_weights_numpy(self):
        return self._learner.get_weights_numpy()

    def get_state(self):
        return self._learner.get_state()

    def set_state(self, state):
        self._learner.set_state(state)
        return True

    def ping(self):
        return True


class LearnerGroup:
    """Reference: `learner_group.py:80`.  num_learners=0 → local learner
    in the driver process (the TPU path: one process, mesh-sharded
    update); num_learners>=1 → remote DDP actors.

    `gang_devices >= 2` builds the pjit learner gang: a 1-D "data" mesh
    over that many local devices, the update compiled once as a single
    sharded program (the production learner shape for BASELINE config
    #3 — see make_data_mesh)."""

    def __init__(self, module: RLModule, loss_fn: Callable, *,
                 num_learners: int = 0, lr: float = 3e-4,
                 grad_clip: Optional[float] = 0.5, seed: int = 0,
                 mesh: Any = None, gang_devices: int = 0):
        self._num = num_learners
        if gang_devices >= 2:
            if num_learners:
                raise ValueError(
                    "gang_devices (mesh-sharded pjit gang) and "
                    "num_learners (DDP actors) are alternative scaling "
                    "axes — set one"
                )
            if mesh is None:
                mesh = make_data_mesh(gang_devices)
        self._gang_devices = (
            int(mesh.devices.size) if mesh is not None else (
                0 if num_learners else 1
            )
        )
        if num_learners == 0:
            self._local = Learner(module, loss_fn, lr, grad_clip, seed, mesh)
            self._actors: List = []
        else:
            self._local = None
            group = f"learner_ddp_{seed}_{id(self)}"
            self._actors = [
                rt.remote(_RemoteLearner).options(num_cpus=1).remote(
                    module, loss_fn, lr, grad_clip, seed, num_learners,
                    rank, group,
                )
                for rank in range(num_learners)
            ]
            rt.get([a.ping.remote() for a in self._actors])

    def update_minibatch_device(self, batch: Dict[str, np.ndarray]
                                ) -> Dict[str, Any]:
        """Sync-free update for the overlap pipeline (local/gang mode
        only; DDP actors already return host floats).  Duration metrics
        are the caller's job — dispatch is async, so wall time is only
        meaningful once the metrics are read back."""
        if self._local is not None:
            return self._local.update_minibatch_device(batch)
        return self.update_minibatch(batch)

    def update_minibatch(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        if self._local is not None:
            return self._local.update_minibatch(batch)
        # split the minibatch across ranks; every rank applies the same
        # allreduced gradient so params stay identical
        n = batch["obs"].shape[0]
        if n < self._num:
            raise ValueError(
                f"minibatch of {n} rows cannot be split across "
                f"{self._num} learners — an empty shard would produce "
                "NaN gradients; raise minibatch_size or lower num_learners"
            )
        shard = n // self._num
        refs = []
        for i, a in enumerate(self._actors):
            sl = slice(i * shard, (i + 1) * shard if i < self._num - 1 else n)
            refs.append(a.update_minibatch.remote(
                {k: v[sl] for k, v in batch.items()}
            ))
        all_metrics = rt.get(refs)
        return {
            k: float(np.mean([m[k] for m in all_metrics]))
            for k in all_metrics[0]
        }

    def get_weights_numpy(self):
        if self._local is not None:
            return self._local.get_weights_numpy()
        return rt.get(self._actors[0].get_weights_numpy.remote())

    def get_state(self):
        if self._local is not None:
            return self._local.get_state()
        return rt.get(self._actors[0].get_state.remote())

    def set_state(self, state):
        if self._local is not None:
            self._local.set_state(state)
        else:
            rt.get([a.set_state.remote(state) for a in self._actors])

    @property
    def num_gang_devices(self) -> int:
        """Mesh width of the pjit gang (1 = single local device,
        0 = DDP actors carry the parallelism instead)."""
        return self._gang_devices

    def stop(self):
        for a in self._actors:
            try:
                rt.kill(a)
            except Exception as e:
                logger.debug("learner actor kill on stop failed: %s", e)
