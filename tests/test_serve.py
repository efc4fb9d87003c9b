"""Serve tests (reference analog: python/ray/serve/tests/ — in-process
controller + proxy per SURVEY §4 tier 4)."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def serve_cluster():
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=8)
    serve.start(http_options={"port": 0})
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _http_get(path, port, timeout=30):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return r.status, r.read()


def _http_post(path, port, payload, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def test_deploy_and_handle_call(serve_cluster):
    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

        def triple(self, x):
            return x * 3

    handle = serve.run(Doubler.bind(), name="doubler",
                       route_prefix="/doubler")
    assert handle.remote(21).result(timeout_s=30) == 42
    assert handle.triple.remote(5).result(timeout_s=30) == 15
    st = serve.status("doubler")
    assert st["status"] == "RUNNING"
    serve.delete("doubler")
    assert serve.status("doubler")["status"] == "NOT_FOUND"


def test_function_deployment_http(serve_cluster):
    @serve.deployment
    def echo(request):
        data = request.json()
        return {"echo": data["msg"], "path": request.path}

    serve.run(echo.bind(), name="echo", route_prefix="/echo")
    port = serve.get_http_port()
    status, body = _http_post("/echo/sub?x=1", port, {"msg": "hi"})
    assert status == 200
    out = json.loads(body)
    assert out == {"echo": "hi", "path": "/sub"}
    # healthz + routes endpoints
    status, body = _http_get("/-/healthz", port)
    assert status == 200 and body == b"success"
    status, body = _http_get("/-/routes", port)
    assert json.loads(body).get("/echo") == "echo"
    serve.delete("echo")


def test_model_composition(serve_cluster):
    @serve.deployment
    class Adder:
        def __init__(self, increment):
            self.increment = increment

        def __call__(self, x):
            return x + self.increment

    @serve.deployment
    class Combiner:
        def __init__(self, a, b):
            self.a = a
            self.b = b

        async def __call__(self, x):
            ra, rb = self.a.remote(x), self.b.remote(x)
            return (await ra) + (await rb)

    app = Combiner.bind(Adder.options(name="Add1").bind(1),
                        Adder.options(name="Add2").bind(2))
    handle = serve.run(app, name="compose", route_prefix="/compose")
    assert handle.remote(10).result(timeout_s=60) == 23  # (10+1)+(10+2)
    serve.delete("compose")


def test_multiple_replicas_and_scaling(serve_cluster):
    @serve.deployment(num_replicas=2, max_ongoing_requests=4)
    class Who:
        def __init__(self):
            import os

            self.pid = os.getpid()

        def __call__(self, _):
            return self.pid

    handle = serve.run(Who.bind(), name="who", route_prefix="/who")
    pids = {handle.remote(None).result(timeout_s=30) for _ in range(20)}
    assert len(pids) == 2  # both replicas served traffic
    serve.delete("who")


def test_replica_death_recovery(serve_cluster):
    @serve.deployment(num_replicas=1, health_check_period_s=0.2)
    class Fragile:
        def __call__(self, cmd):
            if cmd == "die":
                import os
                import threading

                # after the reply: a request that dies with its replica is
                # retried on the replacement (the router's rule for
                # transport errors) and would kill that one too, again and
                # again from the handle's request pool, for its 60 s
                threading.Timer(0.2, os._exit, args=(1,)).start()
                return "dying"
            return "alive"

    handle = serve.run(Fragile.bind(), name="fragile",
                       route_prefix="/fragile")
    assert handle.remote("ping").result(timeout_s=30) == "alive"
    assert handle.remote("die").result(timeout_s=30) == "dying"
    time.sleep(0.5)  # the replica is gone
    # the controller health-checks, replaces the replica, traffic resumes
    deadline = time.monotonic() + 60
    ok = False
    while time.monotonic() < deadline:
        try:
            if handle.remote("ping").result(timeout_s=10) == "alive":
                ok = True
                break
        except Exception:
            time.sleep(0.3)
    assert ok, "replica was not replaced after death"
    serve.delete("fragile")


def test_user_config_reconfigure(serve_cluster):
    @serve.deployment(user_config={"threshold": 1})
    class Thresh:
        def __init__(self):
            self.threshold = None

        def reconfigure(self, cfg):
            self.threshold = cfg["threshold"]

        def __call__(self, _):
            return self.threshold

    serve.run(Thresh.bind(), name="thresh", route_prefix="/thresh")
    h = serve.get_app_handle("thresh")
    assert h.remote(None).result(timeout_s=30) == 1
    serve.delete("thresh")


def test_serve_batch(serve_cluster):
    @serve.deployment(max_ongoing_requests=32)
    class BatchModel:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.1)
        async def predict(self, items):
            self.batch_sizes.append(len(items))
            return [i * 10 for i in items]

        async def __call__(self, x):
            return await self.predict(x)

        def sizes(self):
            return self.batch_sizes

    handle = serve.run(BatchModel.bind(), name="batch",
                       route_prefix="/batch")
    responses = [handle.remote(i) for i in range(16)]
    values = sorted(r.result(timeout_s=30) for r in responses)
    assert values == [i * 10 for i in range(16)]
    sizes = serve.get_deployment_handle(
        "BatchModel", "batch").sizes.remote().result(timeout_s=30)
    assert max(sizes) > 1, f"no batching happened: {sizes}"
    serve.delete("batch")


def test_multiplexed_models(serve_cluster):
    @serve.deployment
    class Multi:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            self.loads.append(model_id)
            return {"id": model_id, "scale": int(model_id[-1])}

        async def __call__(self, x):
            model_id = serve.get_multiplexed_model_id()
            model = await self.get_model(model_id)
            return x * model["scale"]

    handle = serve.run(Multi.bind(), name="multi", route_prefix="/multi")
    h2 = handle.options(multiplexed_model_id="m2")
    h3 = handle.options(multiplexed_model_id="m3")
    assert h2.remote(10).result(timeout_s=30) == 20
    assert h3.remote(10).result(timeout_s=30) == 30
    assert h2.remote(7).result(timeout_s=30) == 14  # cached, no reload
    serve.delete("multi")


def test_autoscaling_up(serve_cluster):
    @serve.deployment(
        max_ongoing_requests=2,
        autoscaling_config={"min_replicas": 1, "max_replicas": 3,
                            "target_ongoing_requests": 1.0,
                            "upscale_delay_s": 0.5,
                            "downscale_delay_s": 60.0},
        health_check_period_s=0.2)
    class Slow:
        def __call__(self, _):
            time.sleep(0.4)
            return "done"

    handle = serve.run(Slow.bind(), name="auto", route_prefix="/auto")
    # flood with concurrent requests to push ongoing above target
    responses = [handle.remote(None) for _ in range(24)]
    deadline = time.monotonic() + 90  # generous: 1-CPU box under suite load
    scaled = False
    while time.monotonic() < deadline:
        st = serve.status("auto")
        if st["deployments"]["Slow"]["replicas"] >= 2:
            scaled = True
            break
        time.sleep(0.3)
    for r in responses:
        r.result(timeout_s=60)
    assert scaled, f"never scaled up: {serve.status('auto')}"
    serve.delete("auto")
