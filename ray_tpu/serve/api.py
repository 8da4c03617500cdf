"""Serve public API: @deployment, bind, run, status, shutdown.

Reference: `python/ray/serve/api.py` (`@serve.deployment:244`,
`serve.run:510`) — deployments are declared with a decorator, composed
into applications with `.bind()`, and deployed by `serve.run`, which
returns a handle to the ingress deployment.
"""

from __future__ import annotations

import inspect
import json
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Union

import ray_tpu as rt
from ray_tpu.serve.config import AutoscalingConfig, DeploymentConfig, GRPCOptions, HTTPOptions
from ray_tpu.serve.slo import SLOConfig
from ray_tpu.serve.controller import (
    CONTROLLER_NAME,
    CONTROLLER_NAMESPACE,
    ServeController,
)
from ray_tpu.serve.handle import DeploymentHandle
from ray_tpu.util import sanitizer as _sanitizer

logger = logging.getLogger(__name__)

_state: Dict[str, Any] = {}
# outermost in the declared order: start() holds it across rt.get()
# while the controller ping round-trips, so runtime._state_lock nests
# inside it (see ray_tpu/util/sanitizer.py for the full order table)
_state_lock = _sanitizer.wrap_lock(
    threading.Lock(), "serve.api._state_lock", _sanitizer.SERVE_STATE_LOCK
)


# ----------------------------------------------------------------------
# deployment declaration
# ----------------------------------------------------------------------
class Application:
    """A bound deployment graph node (reference: the object returned by
    `Deployment.bind`, `serve/deployment.py`)."""

    def __init__(self, deployment: "Deployment", args: tuple, kwargs: dict):
        self.deployment = deployment
        self.args = args
        self.kwargs = kwargs


class Deployment:
    """Reference: `serve/deployment.py` Deployment."""

    def __init__(self, func_or_class, name: str, config: DeploymentConfig,
                 resources: Optional[Dict[str, float]] = None):
        self.func_or_class = func_or_class
        self.name = name
        self.config = config
        self.resources = resources or {}

    def bind(self, *args, **kwargs) -> Application:
        return Application(self, args, kwargs)

    def options(self, **kwargs) -> "Deployment":
        import copy

        cfg = copy.deepcopy(self.config)
        name = kwargs.pop("name", self.name)
        resources = kwargs.pop("ray_actor_options", None) or kwargs.pop(
            "resources", None
        )
        for k, v in kwargs.items():
            if k == "autoscaling_config":
                v = _coerce_autoscaling(v)
            if k == "slo_config":
                v = _coerce_slo(v)
            if hasattr(cfg, k):
                setattr(cfg, k, v)
            else:
                raise TypeError(f"unknown deployment option {k!r}")
        return type(self)(
            self.func_or_class, name, cfg,
            dict(resources) if resources else dict(self.resources),
        )

    def __call__(self, *a, **k):
        raise TypeError(
            "deployments are not directly callable; use .bind() and serve.run"
        )


def _coerce_autoscaling(v) -> Optional[AutoscalingConfig]:
    if v is None or isinstance(v, AutoscalingConfig):
        return v
    return AutoscalingConfig(**v)


def _coerce_slo(v) -> Optional[SLOConfig]:
    if v is None or isinstance(v, SLOConfig):
        return v
    return SLOConfig(**v)


def deployment(
    _func_or_class: Optional[Callable] = None,
    *,
    name: Optional[str] = None,
    num_replicas: Union[int, str, None] = None,
    max_ongoing_requests: int = 16,
    max_queued_requests: int = -1,
    autoscaling_config: Union[AutoscalingConfig, dict, None] = None,
    slo_config: Union[SLOConfig, dict, None] = None,
    user_config: Optional[Any] = None,
    health_check_period_s: float = 2.0,
    health_check_timeout_s: float = 10.0,
    graceful_shutdown_timeout_s: float = 5.0,
    ray_actor_options: Optional[Dict[str, float]] = None,
):
    """Reference: `serve/api.py:244` @serve.deployment."""

    def _wrap(func_or_class):
        n = num_replicas
        auto = _coerce_autoscaling(autoscaling_config)
        if n == "auto":
            auto = auto or AutoscalingConfig(min_replicas=1, max_replicas=8)
            n = None
        cfg = DeploymentConfig(
            num_replicas=n or 1,
            max_ongoing_requests=max_ongoing_requests,
            max_queued_requests=max_queued_requests,
            autoscaling_config=auto,
            slo_config=_coerce_slo(slo_config),
            user_config=user_config,
            health_check_period_s=health_check_period_s,
            health_check_timeout_s=health_check_timeout_s,
            graceful_shutdown_timeout_s=graceful_shutdown_timeout_s,
        )
        return Deployment(
            func_or_class,
            name or getattr(func_or_class, "__name__", "deployment"),
            cfg,
            ray_actor_options,
        )

    if _func_or_class is not None:
        return _wrap(_func_or_class)
    return _wrap


def ingress(_app=None, **_kwargs):
    """FastAPI-style ingress adapter is out of scope; the proxy hands
    plain `serve.Request` objects to the ingress deployment."""

    def _wrap(cls):
        return cls

    return _wrap if _app is None else _app


# ----------------------------------------------------------------------
# controller / proxy lifecycle
# ----------------------------------------------------------------------
def start(http_options: Optional[HTTPOptions] = None, *, proxy: bool = True,
          grpc_options: Optional[Union[GRPCOptions, Dict[str, Any]]] = None):
    """Start the serve control plane (reference: `serve/api.py` serve.start).

    grpc_options (a `GRPCOptions` or `{"host", "port"}` dict) starts
    the generic gRPC ingress alongside HTTP (reference: `gRPCProxy`,
    `proxy.py:545`; see `serve/grpc_proxy.py` for the routing
    contract)."""
    with _state_lock:
        # stale module state survives a full runtime shutdown+restart in
        # the same process (the cached handles point into the DEAD
        # cluster) — validate before reuse, reset if the controller is
        # gone
        c = _state.get("controller")
        if c is not None:
            try:
                rt.get(c.ping.remote(), timeout=10)
            except Exception as e:
                logger.debug("cached serve controller dead (%s); "
                             "resetting serve state", e)
                _state.clear()
                from ray_tpu.serve import handle as _handle_mod

                _handle_mod._close_routers()
        if "controller" not in _state:
            try:
                controller = rt.get_actor(CONTROLLER_NAME, CONTROLLER_NAMESPACE)
            except ValueError:
                controller = (
                    rt.remote(ServeController)
                    .options(
                        name=CONTROLLER_NAME,
                        namespace=CONTROLLER_NAMESPACE,
                        max_concurrency=16,
                        num_cpus=0,
                        # effectively infinite: a crashed controller
                        # restarts and rehydrates from its KV checkpoint
                        # (reference: `controller.py:81-91` recovery)
                        max_restarts=1_000_000_000,
                    )
                    .remote()
                )
                rt.get(controller.ping.remote())
            _state["controller"] = controller
        if proxy and "proxy_fleet" not in _state:
            # per-node proxy fleet (reference: `proxy.py:1140` — one
            # ProxyActor per node): the controller starts/adopts one
            # HTTP proxy per cluster node and keeps the fleet matched
            # to membership in its reconcile loop; addresses land in
            # the KV (`serve:http_addresses`) for discovery
            opts = http_options or HTTPOptions(port=0)
            addrs = rt.get(
                _state["controller"].ensure_proxies.remote(
                    opts.host, opts.port
                ),
                timeout=60,
            )
            _state["proxy_fleet"] = True
            if addrs:
                first = sorted(addrs)[0]
                _state["http_address"] = tuple(addrs[first])
        if grpc_options is not None and "grpc_proxy" not in _state:
            from ray_tpu.serve.config import GRPCOptions
            from ray_tpu.serve.grpc_proxy import GRPCProxy

            if isinstance(grpc_options, dict):
                gopts = GRPCOptions(**grpc_options)
            else:
                gopts = grpc_options
            from ray_tpu.core.runtime import get_runtime

            try:  # another process may already run it (same pattern
                # as the controller above); failed starts leave a
                # named actor that must be reaped before retrying
                gp = rt.get_actor("SERVE_GRPC_PROXY", CONTROLLER_NAMESPACE)
                gport = rt.get(gp.address.remote())[1]
            except ValueError:
                gp = (
                    rt.remote(GRPCProxy)
                    .options(
                        name="SERVE_GRPC_PROXY",
                        namespace=CONTROLLER_NAMESPACE,
                        max_concurrency=16,
                        num_cpus=0,
                    )
                    .remote(gopts.host, gopts.port)
                )
                try:
                    gport = rt.get(gp.start.remote())
                except Exception:
                    rt.kill(gp)
                    raise
            _state["grpc_proxy"] = gp
            _state["grpc_address"] = (gopts.host, gport)
            get_runtime().kv_put(
                "serve:grpc_address",
                json.dumps([gopts.host, gport]).encode(),
            )
    return _state["controller"]


def _get_controller():
    c = _state.get("controller")
    if c is not None:
        return c
    c = rt.get_actor(CONTROLLER_NAME, CONTROLLER_NAMESPACE)
    _state["controller"] = c
    return c


async def _get_controller_async():
    """Loop-thread-safe controller lookup (used by routers/proxies from
    the runtime's io loop, where blocking `rt.get_actor` would deadlock)."""
    c = _state.get("controller")
    if c is not None:
        return c
    from ray_tpu.api import ActorHandle
    from ray_tpu.core.ids import ActorID
    from ray_tpu.core.runtime import get_runtime

    info = await get_runtime().controller.call(
        "get_actor", {"name": CONTROLLER_NAME, "namespace": CONTROLLER_NAMESPACE}
    )
    if info is None or info.get("state") == "DEAD":
        raise RuntimeError("serve controller is not running")
    c = ActorHandle(
        ActorID(info["actor_id"]), info["address"], CONTROLLER_NAME,
        info.get("max_task_retries", 0),
    )
    _state["controller"] = c
    return c


def _discover_address(state_key: str, kv_key: str) -> Optional[tuple]:
    """Cached ingress address; a proxy started by ANOTHER process (REST
    deploy via the dashboard) is discovered through the controller KV."""
    addr = _state.get(state_key)
    if addr is not None:
        return addr
    from ray_tpu.core.runtime import get_runtime, is_initialized

    if not is_initialized():
        return None
    raw = get_runtime().kv_get(kv_key)
    if raw:
        host, port = json.loads(raw)
        _state[state_key] = (host, int(port))
        return _state[state_key]
    return None


def http_address() -> Optional[tuple]:
    return _discover_address("http_address", "serve:http_address")


def http_addresses() -> Dict[str, tuple]:
    """All live proxy addresses, one per cluster node (reference:
    per-node ProxyActors): {node_id: (host, port)}.  Uncached — the
    fleet changes with cluster membership."""
    from ray_tpu.core.runtime import get_runtime, is_initialized

    if not is_initialized():
        return {}
    raw = get_runtime().kv_get("serve:http_addresses")
    if not raw:
        return {}
    return {
        nid: (host, int(port))
        for nid, (host, port) in json.loads(raw).items()
    }


def grpc_address() -> Optional[tuple]:
    return _discover_address("grpc_address", "serve:grpc_address")


# ----------------------------------------------------------------------
# run / shutdown
# ----------------------------------------------------------------------
def _collect_deployments(app: Application, out: Dict[str, dict]):
    """Post-order walk of the bound graph: nested Applications become
    DeploymentHandles passed to the parent's constructor (reference:
    build_app in `serve/_private/build_app.py`)."""

    def _convert(v, app_name):
        if isinstance(v, Application):
            _collect(v)
            return DeploymentHandle(v.deployment.name, app_name)
        return v

    app_name = out["__app_name__"]

    def _collect(node: Application):
        d = node.deployment
        args = tuple(_convert(a, app_name) for a in node.args)
        kwargs = {k: _convert(v, app_name) for k, v in node.kwargs.items()}
        if d.name in out and out[d.name]["callable_def"] is not d.func_or_class:
            raise ValueError(f"duplicate deployment name {d.name!r}")
        out[d.name] = {
            "name": d.name,
            "callable_def": d.func_or_class,
            "init_args": args,
            "init_kwargs": kwargs,
            "config": d.config,
            "resources": d.resources,
        }

    _collect(app)


def _callable_is_streaming(func_or_class) -> bool:
    """True when the deployment's request entrypoint is a generator /
    async generator: its HTTP responses stream chunked."""
    c = func_or_class
    if isinstance(c, type):
        c = inspect.getattr_static(c, "__call__", None)
    return inspect.isgeneratorfunction(c) or inspect.isasyncgenfunction(c)


def run(
    target: Application,
    *,
    name: str = "default",
    route_prefix: Optional[str] = "/",
    wait_for_ready: bool = True,
    timeout_s: float = 60.0,
) -> DeploymentHandle:
    """Deploy an application and return a handle to its ingress
    (reference: `serve/api.py:510` serve.run)."""
    if not isinstance(target, Application):
        raise TypeError("serve.run expects the Application from .bind()")
    from ray_tpu.util.usage_stats import record_library_usage

    record_library_usage("serve")
    controller = start(proxy=True)
    collected: Dict[str, Any] = {"__app_name__": name}
    _collect_deployments(target, collected)
    collected.pop("__app_name__")
    app_config = {
        "name": name,
        "route_prefix": route_prefix,
        "ingress": target.deployment.name,
        "ingress_streaming": _callable_is_streaming(
            target.deployment.func_or_class
        ),
        "deployments": list(collected.values()),
    }
    rt.get(controller.deploy_application.remote(app_config), timeout=timeout_s)
    if wait_for_ready:
        _wait_for_app(controller, name, timeout_s)
    return DeploymentHandle(target.deployment.name, name)


def _wait_for_app(controller, name: str, timeout_s: float):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        status = rt.get(controller.get_serve_status.remote())
        app = status.get(name, {})
        if app and all(
            d["running"] >= 1 and d["running"] >= d["target_replicas"]
            for d in app.values()
        ):
            return
        time.sleep(0.1)
    raise TimeoutError(f"application {name!r} did not become ready")


def delete(name: str):
    controller = _get_controller()
    rt.get(controller.delete_application.remote(name))


def status() -> Dict[str, Any]:
    controller = _get_controller()
    return rt.get(controller.get_serve_status.remote())


def slo_status() -> Dict[str, Any]:
    """Per-deployment SLO burn rates: {app: {deployment: row}} where
    row carries the configured targets, multi-window burn rates folded
    from the replicas' ledger counters, and an `ok` verdict (see
    serve/slo.py).  Deployments without an `slo_config` report
    {"configured": False}."""
    controller = _get_controller()
    return rt.get(controller.get_slo_status.remote())


def get_app_handle(name: str = "default") -> DeploymentHandle:
    controller = _get_controller()
    ingress = rt.get(controller.get_ingress.remote(name))
    if ingress is None:
        raise ValueError(f"no application named {name!r}")
    return DeploymentHandle(ingress, name)


def get_deployment_handle(deployment_name: str, app_name: str = "default"):
    return DeploymentHandle(deployment_name, app_name)


def shutdown():
    """Tear down all applications, the proxy, and the controller."""
    with _state_lock:
        controller = _state.pop("controller", None)
        proxy = _state.pop("proxy", None)
        grpc_proxy = _state.pop("grpc_proxy", None)
        _state.pop("proxy_fleet", None)
        _state.pop("http_address", None)
        _state.pop("grpc_address", None)
    from ray_tpu.serve import handle as _handle_mod

    _handle_mod._close_routers()
    # the control plane may have been started by ANOTHER process (REST
    # deploy via the dashboard): resolve the named actors so shutdown
    # tears them down from anywhere
    if controller is None:
        try:
            controller = rt.get_actor(CONTROLLER_NAME, CONTROLLER_NAMESPACE)
        except Exception as e:
            logger.debug("no serve controller to shut down: %s", e)
            controller = None
    fleet_proxies: List[Any] = []
    if proxy is None:
        try:  # legacy single-proxy deployments
            proxy = rt.get_actor("SERVE_PROXY", CONTROLLER_NAMESPACE)
        except Exception as e:
            logger.debug("no legacy proxy to shut down: %s", e)
            proxy = None
        # per-node fleet: resolvable from anywhere via the KV address
        # map even when the controller itself is unreachable
        try:
            from ray_tpu.core.runtime import get_runtime, is_initialized

            if is_initialized():
                raw = get_runtime().kv_get("serve:http_addresses")
                for nid in (json.loads(raw) if raw else {}):
                    try:
                        fleet_proxies.append(rt.get_actor(
                            f"SERVE_PROXY::{nid}", CONTROLLER_NAMESPACE
                        ))
                    except Exception as e:
                        logger.debug("fleet proxy %s gone: %s", nid, e)
        except Exception as e:
            logger.debug("fleet proxy discovery failed: %s", e)
    if grpc_proxy is None:
        try:
            grpc_proxy = rt.get_actor("SERVE_GRPC_PROXY",
                                      CONTROLLER_NAMESPACE)
        except Exception as e:
            logger.debug("no grpc proxy to shut down: %s", e)
            grpc_proxy = None
    try:
        from ray_tpu.core.runtime import get_runtime, is_initialized

        if is_initialized():
            get_runtime().kv_del("serve:http_address")
            get_runtime().kv_del("serve:http_addresses")
            get_runtime().kv_del("serve:grpc_address")
    except Exception as e:
        logger.debug("clearing serve address keys failed: %s", e)
    for p in (proxy, grpc_proxy, *fleet_proxies):
        if p is not None:
            try:
                rt.get(p.stop.remote(), timeout=5)
            except Exception as e:
                logger.debug("proxy stop failed: %s", e)
            try:
                rt.kill(p)
            except Exception as e:
                logger.debug("proxy kill failed: %s", e)
    if controller is not None:
        try:
            rt.get(controller.shutdown.remote(), timeout=30)
        except Exception as e:
            logger.debug("controller shutdown call failed: %s", e)
        try:
            rt.kill(controller)
        except Exception as e:
            logger.debug("controller kill failed: %s", e)
    # clear the FT snapshot only once the controller is dead: its own
    # _checkpoint calls would recreate the key, and a timed-out teardown
    # must not leave a snapshot that resurrects deleted apps on the next
    # serve.start()
    try:
        from ray_tpu.core.runtime import get_runtime, is_initialized
        from ray_tpu.serve.controller import STATE_KV_KEY

        if is_initialized():
            get_runtime().kv_del(STATE_KV_KEY)
    except Exception as e:
        logger.debug("clearing serve FT snapshot failed: %s", e)
    from ray_tpu.serve import handle as _h

    _h._close_routers()
