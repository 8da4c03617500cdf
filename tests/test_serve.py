"""Serve tests.

Coverage modeled on the reference's `python/ray/serve/tests/`:
deploy + handle calls, model composition, HTTP ingress over a real
socket, batching, autoscaling, replica replacement (`test_deploy.py`,
`test_handle.py`, `test_proxy.py`, `test_batching.py`,
`test_autoscaling_policy.py`).
"""

import json
import time
import urllib.request

import pytest

import ray_tpu as rt
from ray_tpu import serve


@pytest.fixture(scope="module")
def cluster():
    rt.init(num_workers=4, num_cpus=16, ignore_reinit_error=True)
    yield
    serve.shutdown()
    rt.shutdown()


@pytest.fixture()
def serve_instance(cluster):
    yield
    for app in list(serve.status()):
        serve.delete(app)


def _http_get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read()


def _http_post(url, data: bytes, timeout=10):
    req = urllib.request.Request(url, data=data, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


@pytest.mark.sanitize  # serve smoke: tier-1 sanitized subset
def test_deploy_and_handle_call(serve_instance):
    @serve.deployment
    class Echo:
        def __call__(self, x):
            return {"echo": x}

    h = serve.run(Echo.bind(), name="echo", route_prefix="/echo")
    assert h.remote("hi").result(timeout_s=10) == {"echo": "hi"}
    # named method call
    assert serve.status()["echo"]["Echo"]["running"] == 1


@pytest.mark.sanitize  # serve smoke: tier-1 sanitized subset
def test_function_deployment_and_http(serve_instance):
    @serve.deployment
    def square(request):
        n = int(request.query_params.get("n", "0"))
        return {"out": n * n}

    serve.run(square.bind(), name="sq", route_prefix="/sq")
    host, port = serve.http_address()
    status, body = _http_get(f"http://{host}:{port}/sq?n=7")
    assert status == 200
    assert json.loads(body) == {"out": 49}


@pytest.mark.sanitize  # serve smoke: tier-1 sanitized subset
def test_composition_sync_handles(serve_instance):
    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Adder:
        def __init__(self, doubler, offset):
            self._d = doubler
            self._off = offset

        def __call__(self, x):
            return self._d.remote(x).result() + self._off

    app = Adder.bind(Doubler.bind(), 5)
    h = serve.run(app, name="compose", route_prefix="/compose")
    assert h.remote(10).result(timeout_s=10) == 25


def test_composition_async_and_response_passing(serve_instance):
    @serve.deployment
    class Up:
        def __call__(self, s):
            return s.upper()

    @serve.deployment
    class Excl:
        def __call__(self, s):
            return s + "!"

    @serve.deployment
    class Chain:
        def __init__(self, up, excl):
            self._up = up
            self._excl = excl

        async def __call__(self, s):
            # pass one response as the argument of the next call —
            # resolved to its value before Excl executes
            r1 = self._up.remote(s)
            return await self._excl.remote(r1)

    h = serve.run(Chain.bind(Up.bind(), Excl.bind()), name="chain",
                  route_prefix="/chain")
    assert h.remote("hey").result(timeout_s=10) == "HEY!"


def test_multi_replica_load_balancing(serve_instance):
    @serve.deployment(num_replicas=2)
    class WhoAmI:
        def __init__(self):
            import os

            self._pid = os.getpid()

        def __call__(self, _x=None):
            return self._pid

    h = serve.run(WhoAmI.bind(), name="who", route_prefix="/who")
    pids = {h.remote().result(timeout_s=10) for _ in range(20)}
    assert len(pids) == 2  # both replicas served traffic


def test_batching(serve_instance):
    @serve.deployment
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
        async def handle(self, items):
            self.batch_sizes.append(len(items))
            return [x * 10 for x in items]

        async def __call__(self, x):
            return await self.handle(x)

        def sizes(self):
            return self.batch_sizes

    h = serve.run(Batched.bind(), name="batched", route_prefix="/batched")
    responses = [h.remote(i) for i in range(8)]
    values = sorted(r.result(timeout_s=15) for r in responses)
    assert values == [i * 10 for i in range(8)]
    sizes = h.sizes.remote().result(timeout_s=10)
    assert max(sizes) > 1  # requests were actually batched


def test_http_post_json_and_response_type(serve_instance):
    @serve.deployment
    class Api:
        def __call__(self, request):
            data = request.json()
            return serve.Response(
                {"sum": sum(data["xs"])}, status_code=201
            )

    serve.run(Api.bind(), name="api", route_prefix="/api")
    host, port = serve.http_address()
    req = urllib.request.Request(
        f"http://{host}:{port}/api",
        data=json.dumps({"xs": [1, 2, 3]}).encode(),
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 201
        assert json.loads(r.read()) == {"sum": 6}


def test_http_404(serve_instance):
    serve.start()
    host, port = serve.http_address()
    try:
        _http_get(f"http://{host}:{port}/definitely-not-a-route")
        raise AssertionError("expected 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_autoscaling_up_and_down(serve_instance):
    @serve.deployment(
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "target_ongoing_requests": 1,
            "upscale_delay_s": 0.2,
            "downscale_delay_s": 0.5,
        },
        max_ongoing_requests=4,
    )
    class Slow:
        def __call__(self, _x=None):
            time.sleep(0.4)
            return "done"

    h = serve.run(Slow.bind(), name="auto", route_prefix="/auto")
    assert serve.status()["auto"]["Slow"]["running"] == 1
    # push sustained concurrent load
    responses = [h.remote(i) for i in range(40)]
    deadline = time.time() + 30
    scaled_up = False
    while time.time() < deadline:
        if serve.status()["auto"]["Slow"]["running"] >= 2:
            scaled_up = True
            break
        time.sleep(0.2)
    for r in responses:
        r.result(timeout_s=60)
    assert scaled_up, "deployment never scaled above 1 replica"
    # idle → back down to min_replicas
    deadline = time.time() + 30
    while time.time() < deadline:
        if serve.status()["auto"]["Slow"]["target_replicas"] == 1:
            break
        time.sleep(0.2)
    assert serve.status()["auto"]["Slow"]["target_replicas"] == 1


def test_replica_replaced_after_death(serve_instance):
    @serve.deployment
    class Fragile:
        def __init__(self):
            import os

            self._pid = os.getpid()

        def __call__(self, _x=None):
            return self._pid

        def die(self):
            import os

            os._exit(1)

    h = serve.run(Fragile.bind(), name="fragile", route_prefix="/fragile")
    pid1 = h.remote().result(timeout_s=10)
    try:
        h.die.remote().result(timeout_s=5)
    except Exception:
        pass
    # controller should notice the dead replica and start a fresh one
    deadline = time.time() + 30
    pid2 = None
    while time.time() < deadline:
        try:
            pid2 = h.remote().result(timeout_s=5)
            if pid2 != pid1:
                break
        except Exception:
            time.sleep(0.3)
    assert pid2 is not None and pid2 != pid1


def test_redeploy_updates_version(serve_instance):
    @serve.deployment
    class V:
        def __call__(self, _x=None):
            return "v1"

    serve.run(V.bind(), name="vers", route_prefix="/vers")

    @serve.deployment(name="V")
    class V2:
        def __call__(self, _x=None):
            return "v2"

    h = serve.run(V2.bind(), name="vers", route_prefix="/vers")
    deadline = time.time() + 15
    while time.time() < deadline:
        if h.remote().result(timeout_s=10) == "v2":
            return
        time.sleep(0.2)
    raise AssertionError("redeploy never served v2")


def test_model_multiplexing(serve_instance):
    @serve.deployment
    class MultiModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            self.loads.append(model_id)
            return {"model": model_id, "weight": len(model_id)}

        async def __call__(self, x):
            model = await self.get_model()
            return f"{model['model']}:{x * model['weight']}"

        def load_log(self):
            return self.loads

    h = serve.run(MultiModel.bind(), name="mux", route_prefix="/mux")
    ha = h.options(multiplexed_model_id="alpha")
    hb = h.options(multiplexed_model_id="beta")
    assert ha.remote(2).result(timeout_s=10) == "alpha:10"
    assert hb.remote(2).result(timeout_s=10) == "beta:8"
    # cached: repeated calls do not reload
    assert ha.remote(3).result(timeout_s=10) == "alpha:15"
    loads = h.load_log.remote().result(timeout_s=10)
    assert loads.count("alpha") == 1 and loads.count("beta") == 1
    # LRU: a third model evicts the least recently USED (beta — alpha
    # was touched after it); re-requesting beta reloads it
    h.options(multiplexed_model_id="gamma").remote(1).result(timeout_s=10)
    ha.remote(1).result(timeout_s=10)  # alpha still resident: no reload
    hb.remote(1).result(timeout_s=10)  # beta was evicted: reloads
    loads = h.load_log.remote().result(timeout_s=10)
    assert loads.count("alpha") == 1
    assert loads.count("beta") == 2


# ----------------------------------------------------------------------
# streaming (reference: serve streaming responses via generators,
# `replica.py:463-492` handle_request_streaming; handle stream=True)
# ----------------------------------------------------------------------
def test_handle_streaming(serve_instance):
    @serve.deployment
    class Tokens:
        def stream(self, n):
            for i in range(n):
                yield f"tok{i}"

        def __call__(self, req):
            return "ok"

    serve.run(Tokens.bind(), name="tok", route_prefix="/tok")
    h = serve.get_app_handle("tok").options(stream=True)
    out = list(h.stream.remote(4))
    assert out == ["tok0", "tok1", "tok2", "tok3"]


def test_http_streaming_chunked(serve_instance):
    @serve.deployment
    def counter(request):
        for i in range(3):
            yield f"line-{i}\n"

    serve.run(counter.bind(), name="streamapp", route_prefix="/streamapp")
    # raw socket: observe the chunked framing
    import socket

    host, port = serve.http_address()
    s = socket.create_connection((host, port), timeout=15)
    s.sendall(b"GET /streamapp HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n")
    data = b""
    while True:
        b_ = s.recv(65536)
        if not b_:
            break
        data += b_
    s.close()
    head, _, body = data.partition(b"\r\n\r\n")
    assert b"Transfer-Encoding: chunked" in head
    # de-chunk
    text = b""
    rest = body
    while rest:
        size_line, _, rest = rest.partition(b"\r\n")
        n = int(size_line, 16)
        if n == 0:
            break
        text += rest[:n]
        rest = rest[n + 2:]
    assert text == b"line-0\nline-1\nline-2\n"


def test_streaming_incremental_over_handle(serve_instance):
    @serve.deployment
    class Slow:
        def gen(self):
            yield "a"
            time.sleep(2.0)
            yield "b"

        def __call__(self, req):
            return "ok"

    serve.run(Slow.bind(), name="slowstream", route_prefix="/slowstream")
    h = serve.get_app_handle("slowstream").options(stream=True)
    g = iter(h.gen.remote())
    t0 = time.time()
    assert next(g) == "a"
    assert time.time() - t0 < 1.5  # first item before the generator ends
    assert next(g) == "b"
    with pytest.raises(StopIteration):
        next(g)


def test_http_streaming_error_before_first_item_is_500(serve_instance):
    @serve.deployment
    def badstream(request):
        raise RuntimeError("pre-stream boom")
        yield "never"  # noqa — makes this a generator function

    serve.run(badstream.bind(), name="badstream", route_prefix="/badstream")
    import urllib.error

    host, port = serve.http_address()
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(f"http://{host}:{port}/badstream", timeout=15)
    assert e.value.code == 500


# ----------------------------------------------------------------------
# gRPC ingress (reference: gRPCProxy, serve/_private/proxy.py:545)
# ----------------------------------------------------------------------
def test_grpc_proxy_roundtrip(serve_instance):
    import grpc

    from ray_tpu.serve.config import GRPCOptions

    @serve.deployment
    class EchoUpper:
        def __call__(self, request):
            # request.body() = the raw gRPC request bytes
            return request.body().upper()

    serve.start(grpc_options=GRPCOptions(port=0))
    serve.run(EchoUpper.bind(), name="grpcecho", route_prefix="/grpcecho")
    host, port = serve.grpc_address()

    channel = grpc.insecure_channel(f"{host}:{port}")
    call = channel.unary_unary(
        "/grpcecho/__call__",
        request_serializer=None,
        response_deserializer=None,
    )
    assert call(b"hello grpc", timeout=60) == b"HELLO GRPC"

    # reserved service surface
    health = channel.unary_unary("/ray.serve.ServeAPIService/Healthz",
                                 request_serializer=None,
                                 response_deserializer=None)
    assert health(b"", timeout=30) == b"ok"
    apps = channel.unary_unary(
        "/ray.serve.ServeAPIService/ListApplications",
        request_serializer=None, response_deserializer=None,
    )
    assert "grpcecho" in json.loads(apps(b"", timeout=30))

    # unknown application -> NOT_FOUND status
    import pytest as _pytest

    missing = channel.unary_unary("/nosuchapp/__call__",
                                  request_serializer=None,
                                  response_deserializer=None)
    with _pytest.raises(grpc.RpcError) as exc_info:
        missing(b"x", timeout=30)
    assert exc_info.value.code() == grpc.StatusCode.NOT_FOUND
    channel.close()
    serve.delete("grpcecho")


def test_controller_crash_recovers(serve_instance):
    """Kill the controller process; serving continues from the routers'
    cached tables through the outage, the restarted controller
    rehydrates from its KV checkpoint, re-adopts the SAME live replicas
    (no replica churn), and the control plane works again (reference:
    `serve/_private/controller.py:81-91` checkpoint recovery)."""
    from ray_tpu.serve.api import CONTROLLER_NAME, CONTROLLER_NAMESPACE

    @serve.deployment(num_replicas=2)
    class Steady:
        def __init__(self):
            import os

            self._pid = os.getpid()

        def __call__(self, _x=None):
            return self._pid

    h = serve.run(Steady.bind(), name="steady", route_prefix="/steady")
    pids_before = {h.remote().result(timeout_s=10) for _ in range(20)}
    assert len(pids_before) == 2

    controller = rt.get_actor(CONTROLLER_NAME, CONTROLLER_NAMESPACE)
    rt.kill(controller, no_restart=False)  # crash, not graceful teardown

    # data plane keeps serving from cached routing tables DURING the
    # controller outage/restart window
    for _ in range(10):
        assert h.remote().result(timeout_s=10) in pids_before

    # control plane comes back and rehydrates
    deadline = time.time() + 60
    status = {}
    while time.time() < deadline:
        try:
            status = serve.status()
            if status.get("steady", {}).get("Steady", {}).get("running") == 2:
                break
        except Exception:
            pass
        time.sleep(0.5)
    assert status["steady"]["Steady"]["running"] == 2

    # the SAME replicas were re-adopted — no replica churn on recovery
    pids_after = {h.remote().result(timeout_s=10) for _ in range(20)}
    assert pids_after == pids_before

    # the recovered controller still reconciles: kill a replica, it is
    # replaced
    victim = rt.get_actor(
        "SERVE_REPLICA::steady#Steady#0", CONTROLLER_NAMESPACE
    )
    rt.kill(victim)
    deadline = time.time() + 30
    while time.time() < deadline:
        pids_now = set()
        try:
            pids_now = {h.remote().result(timeout_s=5) for _ in range(8)}
        except Exception:
            pass
        if len(pids_now) == 2 and pids_now != pids_before:
            break
        time.sleep(0.5)
    assert len(pids_now) == 2 and pids_now != pids_before


# ---------------------------------------------------------------------------
# declarative config schema (reference: serve/schema.py)
# ---------------------------------------------------------------------------
def test_schema_validation():
    from ray_tpu.serve import schema as ss

    doc = ss.ServeDeploySchema.model_validate({"applications": [{
        "name": "a1", "route_prefix": "/a", "import_path": "m.sub:app",
        "deployments": [
            {"name": "D", "num_replicas": 3,
             "ray_actor_options": {"num_cpus": 2, "resources": {"x": 1}}},
        ],
    }]})
    ov = doc.applications[0].deployments[0].override_kwargs()
    assert ov["num_replicas"] == 3
    assert ov["ray_actor_options"] == {"num_cpus": 2,
                                       "resources": {"x": 1}}
    # runtime_env survives as a real actor option, never a resource
    d2 = ss.DeploymentSchema.model_validate({
        "name": "D", "ray_actor_options": {
            "runtime_env": {"env_vars": {"A": "1"}}}})
    assert d2.override_kwargs()["ray_actor_options"] == {
        "runtime_env": {"env_vars": {"A": "1"}}}

    with pytest.raises(Exception):  # bad import path
        ss.ServeApplicationSchema.model_validate({"import_path": "nocolon"})
    with pytest.raises(Exception):  # unknown field (extra=forbid)
        ss.ServeApplicationSchema.model_validate(
            {"import_path": "m:a", "bogus": 1})
    with pytest.raises(Exception):  # duplicate app names
        ss.ServeDeploySchema.model_validate({"applications": [
            {"name": "x", "import_path": "m:a", "route_prefix": "/1"},
            {"name": "x", "import_path": "m:b", "route_prefix": "/2"},
        ]})
    with pytest.raises(Exception):  # duplicate route prefixes
        ss.ServeDeploySchema.model_validate({"applications": [
            {"name": "x", "import_path": "m:a", "route_prefix": "/1"},
            {"name": "y", "import_path": "m:b", "route_prefix": "/1"},
        ]})
    # num_replicas auto expands to an autoscaling config
    d = ss.DeploymentSchema.model_validate(
        {"name": "D", "num_replicas": "auto"})
    ov = d.override_kwargs()
    assert "num_replicas" not in ov
    assert ov["autoscaling_config"].max_replicas == 8


def test_schema_overrides_applied_e2e(serve_instance, tmp_path):
    """Config-file overrides (replica count) beat the code default,
    nested composition graphs are rewritten node-by-node."""
    import sys

    from ray_tpu.serve import schema as ss

    mod_dir = str(tmp_path)
    with open(tmp_path / "schema_app_mod.py", "w") as f:
        f.write(
            "from ray_tpu import serve\n"
            "@serve.deployment\n"
            "class Inner:\n"
            "    def ping(self):\n"
            "        return 'inner'\n"
            "@serve.deployment\n"
            "class Outer:\n"
            "    def __init__(self, inner):\n"
            "        self.inner = inner\n"
            "    async def __call__(self, request):\n"
            "        return await self.inner.ping.remote()\n"
            "app = Outer.bind(Inner.bind())\n"
        )
    names = ss.deploy_from_schema({"applications": [{
        "name": "schemaapp",
        "route_prefix": "/schema",
        "import_path": "schema_app_mod:app",
        "import_dirs": [mod_dir],
        "deployments": [{"name": "Outer", "num_replicas": 2}],
    }]})
    assert names == ["schemaapp"]
    try:
        status = serve.status()["schemaapp"]
        assert status["Outer"]["target_replicas"] == 2
        assert status["Inner"]["target_replicas"] == 1
        host, port = serve.http_address()
        _, body = _http_get(f"http://{host}:{port}/schema")
        assert b"inner" in body
    finally:
        serve.delete("schemaapp")
        sys.modules.pop("schema_app_mod", None)


def test_request_stats_flow_to_status(serve_instance):
    """Router-piggybacked cumulative request stats fold into monotonic
    per-deployment totals the status (and the Prometheus series) read
    (reference: handle metrics pusher feeding serve observability)."""
    @serve.deployment
    class Stats:
        def __call__(self, request):
            return "ok"

    serve.run(Stats.bind(), name="statsapp", route_prefix="/stats")
    try:
        host, port = serve.http_address()
        for _ in range(5):
            _http_get(f"http://{host}:{port}/stats")
        deadline = time.time() + 15
        completed = 0
        while time.time() < deadline:
            info = serve.status()["statsapp"]["Stats"]
            completed = info.get("completed", 0)
            if completed >= 5:
                break
            time.sleep(0.3)
        assert completed >= 5, info
        assert info["latency_sum_s"] > 0
        # monotonic: more traffic only increases it
        for _ in range(3):
            _http_get(f"http://{host}:{port}/stats")
        deadline = time.time() + 15
        while time.time() < deadline:
            info2 = serve.status()["statsapp"]["Stats"]
            if info2.get("completed", 0) >= completed + 3:
                break
            time.sleep(0.3)
        assert info2["completed"] >= completed + 3
    finally:
        serve.delete("statsapp")


def test_request_stats_reset_on_redeploy(serve_instance):
    """A surviving handle's lifetime counters must not credit a
    redeployed app with the previous incarnation's traffic."""
    @serve.deployment
    class V:
        def __call__(self, _x=None):
            return "v"

    h = serve.run(V.bind(), name="redep", route_prefix="/redep")
    for _ in range(4):
        h.remote().result(timeout_s=10)
    deadline = time.time() + 15
    while time.time() < deadline:
        if serve.status()["redep"]["V"].get("completed", 0) >= 4:
            break
        time.sleep(0.3)
    assert serve.status()["redep"]["V"]["completed"] >= 4

    # redeploy the SAME app/deployment names
    h2 = serve.run(V.bind(), name="redep", route_prefix="/redep")
    h2.remote().result(timeout_s=10)
    deadline = time.time() + 15
    completed = None
    while time.time() < deadline:
        completed = serve.status()["redep"]["V"].get("completed", 0)
        if completed >= 1:
            break
        time.sleep(0.3)
    # fresh incarnation: counts start over (NOT >= 5 from old traffic)
    assert 1 <= completed < 4, completed


# ---------------------------------------------------------------------------
# data-plane parity: per-node proxy fleet, pushed routing tables,
# per-replica metrics (reference: proxy.py:1140 ProxyActor per node,
# long_poll.py pushed tables, serve/metrics.py replica series)
# ---------------------------------------------------------------------------
def test_routing_tables_are_pushed(serve_instance):
    """Routers learn of redeploys via the serve:routes pubsub push —
    NOT by polling: with the poll period forced far out, a redeploy
    must still reach the router within a couple seconds."""
    from ray_tpu.serve.router import Router

    @serve.deployment
    class V1:
        def __call__(self, _=None):
            return "v1"

    @serve.deployment(name="V1")
    class V2:
        def __call__(self, _=None):
            return "v2"

    old_period = Router.REFRESH_PERIOD_S
    Router.REFRESH_PERIOD_S = 300.0  # effectively disable polling
    try:
        h = serve.run(V1.bind(), name="pushapp", route_prefix="/pushapp")
        assert h.remote().result(timeout_s=10) == "v1"
        h2 = serve.run(V2.bind(), name="pushapp", route_prefix="/pushapp")
        deadline = time.time() + 10
        got = None
        while time.time() < deadline:
            got = h2.remote().result(timeout_s=10)
            if got == "v2":
                break
            time.sleep(0.2)
        assert got == "v2", got  # only the push could have delivered this
    finally:
        Router.REFRESH_PERIOD_S = old_period
        serve.delete("pushapp")


def test_per_replica_metrics_exported(serve_instance):
    """Per-replica request counters/latency flow replica -> controller
    (piggybacked on health checks) -> /metrics Prometheus series."""
    @serve.deployment(num_replicas=2)
    class M:
        def __call__(self, _=None):
            return "m"

    h = serve.run(M.bind(), name="mapp", route_prefix="/mapp")
    try:
        for _ in range(6):
            h.remote().result(timeout_s=10)
        from ray_tpu.serve.api import _get_controller

        controller = _get_controller()
        deadline = time.time() + 20
        per = {}
        while time.time() < deadline:
            per = rt.get(controller.get_replica_metrics.remote())
            reps = per.get("mapp", {}).get("M", {})
            if sum(m.get("total", 0) for m in reps.values()) >= 6:
                break
            time.sleep(0.3)
        reps = per["mapp"]["M"]
        assert sum(m["total"] for m in reps.values()) >= 6
        for m in reps.values():
            assert "latency_buckets" in m and "latency_sum_s" in m
        # the Prometheus exporter renders per-replica series (drive
        # it the way the dashboard does: ctl = controller-call coro)
        import asyncio as _aio

        from ray_tpu.core.runtime import get_runtime
        from ray_tpu.dashboard.grafana import update_builtin_metrics
        from ray_tpu.util.metrics import export_text

        rtm = get_runtime()

        async def _ctl(m, payload=None):
            return await _aio.wrap_future(
                _aio.run_coroutine_threadsafe(
                    rtm.controller.call(m, payload), rtm.loop
                )
            )

        async def _drive():
            return await update_builtin_metrics(_ctl)

        _aio.run_coroutine_threadsafe(_drive(), rtm.loop).result(30)
        text = export_text()
        assert "rt_serve_replica_requests_total" in text
        assert 'le="+Inf"' in text
    finally:
        serve.delete("mapp")
