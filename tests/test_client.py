"""ray:// client-mode tests (reference parity:
python/ray/tests/test_client.py — remote tasks, puts, actors, named actors,
errors over the client connection)."""

import numpy as np
import pytest

import ray_tpu

# The in-process client server keeps every ref of a connected client on the
# client's behalf (`ClientServer._track`) and drops them when the client
# releases them, which it does in a batch with its NEXT call, or disconnects
# (`_on_disconnect`): a ref a test let go is still the server's at that
# test's teardown, by design, and none outlives the client.
pytestmark = pytest.mark.ref_leaks_ok


@pytest.fixture(scope="module")
def client_ctx():
    from ray_tpu.util.client import serve

    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=4)
    server = serve(host="127.0.0.1", port=0)
    ctx = ray_tpu.init(address=f"ray://127.0.0.1:{server.port}")
    yield ctx
    ctx.disconnect()
    server.stop()
    ray_tpu.shutdown()


def test_client_task(client_ctx):
    @client_ctx.remote
    def add(a, b):
        return a + b

    assert client_ctx.get(add.remote(2, 3), timeout=60) == 5


def test_client_put_get_roundtrip(client_ctx):
    arr = np.arange(10000, dtype=np.float32)
    ref = client_ctx.put(arr)
    out = client_ctx.get(ref, timeout=60)
    assert np.array_equal(out, arr)


def test_client_ref_as_task_arg(client_ctx):
    ref = client_ctx.put(21)

    @client_ctx.remote
    def double(x):
        return x * 2

    assert client_ctx.get(double.remote(ref), timeout=60) == 42


def test_client_task_error_propagates(client_ctx):
    @client_ctx.remote
    def boom():
        raise ValueError("client-visible error")

    with pytest.raises(Exception, match="client-visible error"):
        client_ctx.get(boom.remote(), timeout=60)


def test_client_actor(client_ctx):
    @client_ctx.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote()
    assert client_ctx.get(c.inc.remote(), timeout=60) == 1
    assert client_ctx.get(c.inc.remote(5), timeout=60) == 6
    client_ctx.kill(c)


def test_client_named_actor(client_ctx):
    @client_ctx.remote
    class Store:
        def __init__(self):
            self.v = "named-ok"

        def read(self):
            return self.v

    Store.options(name="client_named", lifetime="detached").remote()
    h = client_ctx.get_actor("client_named")
    assert client_ctx.get(h.read.remote(), timeout=60) == "named-ok"


def test_client_wait(client_ctx):
    import time

    @client_ctx.remote
    def fast():
        return "f"

    @client_ctx.remote
    def slow():
        time.sleep(5)
        return "s"

    f, s = fast.remote(), slow.remote()
    ready, not_ready = client_ctx.wait([f, s], num_returns=1, timeout=30)
    assert len(ready) == 1 and ready[0].hex() == f.hex()


def test_client_cluster_info(client_ctx):
    assert client_ctx.cluster_resources().get("CPU", 0) > 0
    assert any(n["alive"] for n in client_ctx.nodes())
