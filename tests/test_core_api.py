"""Core API integration tests: tasks, objects, errors
(reference test parity: python/ray/tests/test_basic*.py)."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import GetTimeoutError, RayTaskError


@ray_tpu.remote
def add(a, b):
    return a + b


@ray_tpu.remote
def double(x):
    return x * 2


class TestTasks:
    def test_simple_task(self, ray_start_regular):
        assert ray_tpu.get(add.remote(1, 2)) == 3

    def test_kwargs(self, ray_start_regular):
        assert ray_tpu.get(add.remote(a=5, b=6)) == 11

    def test_many_tasks(self, ray_start_regular):
        refs = [double.remote(i) for i in range(50)]
        assert ray_tpu.get(refs) == [i * 2 for i in range(50)]

    def test_task_chain(self, ray_start_regular):
        ref = double.remote(1)
        for _ in range(5):
            ref = double.remote(ref)
        assert ray_tpu.get(ref) == 64

    def test_num_returns(self, ray_start_regular):
        @ray_tpu.remote(num_returns=3)
        def three():
            return 1, 2, 3

        a, b, c = three.remote()
        assert ray_tpu.get([a, b, c]) == [1, 2, 3]

    def test_options_override(self, ray_start_regular):
        r = add.options(num_returns=1, name="custom_add").remote(2, 3)
        assert ray_tpu.get(r) == 5

    def test_a_worker_is_executed_with_whole_malloc_heaps(
            self, ray_start_regular):
        """``config.whole_malloc_heaps``: the variables are in the
        environment the worker's process, or the template it was forked
        from, was EXECUTED with (``ptmalloc`` reads them once, at the
        start), and a launcher's own choice stands."""
        from ray_tpu._private.config import whole_malloc_heaps

        @ray_tpu.remote
        def executed_with():
            import os

            def of(pid):
                with open(f"/proc/{pid}/environ", "rb") as f:
                    return dict(kv.split(b"=", 1) for kv in
                                f.read().split(b"\0") if b"=" in kv)
            # a forked worker keeps its template's malloc: its parent's
            return [(of(pid).get(b"MALLOC_TOP_PAD_"),
                     of(pid).get(b"MALLOC_MMAP_THRESHOLD_"))
                    for pid in (os.getpid(), os.getppid())]

        assert (b"67108864", b"33554432") in ray_tpu.get(
            executed_with.remote())
        assert whole_malloc_heaps({}) == {
            "MALLOC_TOP_PAD_": "67108864",
            "MALLOC_MMAP_THRESHOLD_": "33554432"}
        assert whole_malloc_heaps({"MALLOC_TOP_PAD_": "0"}) == {
            "MALLOC_TOP_PAD_": "0", "MALLOC_MMAP_THRESHOLD_": "33554432"}

    def test_error_propagation(self, ray_start_regular):
        @ray_tpu.remote
        def fail():
            raise ZeroDivisionError("div")

        with pytest.raises(ZeroDivisionError):
            ray_tpu.get(fail.remote())

    def test_error_with_unpicklable_cause(self, ray_start_regular):
        @ray_tpu.remote
        def fail():
            class Weird(Exception):
                pass

            raise Weird("local class")

        with pytest.raises(RayTaskError):
            ray_tpu.get(fail.remote())

    def test_large_args_and_returns(self, ray_start_regular):
        arr = np.random.rand(500_000)

        @ray_tpu.remote
        def process(x):
            return x * 2

        out = ray_tpu.get(process.remote(arr))
        np.testing.assert_allclose(out, arr * 2)

    def test_nested_tasks(self, ray_start_regular):
        @ray_tpu.remote
        def outer(x):
            return ray_tpu.get(double.remote(x)) + 1

        assert ray_tpu.get(outer.remote(10), timeout=60) == 21

    def test_dependency_passing(self, ray_start_regular):
        big = ray_tpu.put(np.ones(300_000))

        @ray_tpu.remote
        def consume(x):
            return float(x.sum())

        assert ray_tpu.get(consume.remote(big)) == 300_000.0

    def test_ref_in_container_arg(self, ray_start_regular):
        inner_ref = ray_tpu.put(42)

        @ray_tpu.remote
        def unwrap(d):
            return ray_tpu.get(d["ref"])

        assert ray_tpu.get(unwrap.remote({"ref": inner_ref}), timeout=60) == 42

    def test_get_timeout(self, ray_start_regular):
        @ray_tpu.remote
        def sleepy():
            time.sleep(10)

        with pytest.raises(GetTimeoutError):
            ray_tpu.get(sleepy.remote(), timeout=0.5)


class TestObjects:
    def test_put_get_small(self, ray_start_regular):
        ref = ray_tpu.put({"k": 1})
        assert ray_tpu.get(ref) == {"k": 1}

    def test_put_get_large(self, ray_start_regular):
        arr = np.random.rand(1_000_000)
        out = ray_tpu.get(ray_tpu.put(arr))
        np.testing.assert_array_equal(arr, out)

    def test_get_same_ref_twice(self, ray_start_regular):
        ref = ray_tpu.put([1, 2, 3])
        assert ray_tpu.get(ref) == ray_tpu.get(ref)

    def test_put_of_ref_rejected(self, ray_start_regular):
        ref = ray_tpu.put(1)
        with pytest.raises(TypeError):
            ray_tpu.put(ref)

    def test_wait(self, ray_start_regular):
        @ray_tpu.remote
        def sleepy(t):
            time.sleep(t)
            return t

        # wide margins: the CI box is cpu-shares throttled and a burst can
        # delay worker dispatch by seconds — fast must land inside the
        # timeout, slow must not, under that noise
        fast = sleepy.remote(0.05)
        slow = sleepy.remote(15)
        ready, not_ready = ray_tpu.wait([fast, slow], num_returns=1, timeout=8)
        assert ready == [fast]
        assert not_ready == [slow]

    def test_wait_all_ready(self, ray_start_regular):
        refs = [double.remote(i) for i in range(4)]
        ready, not_ready = ray_tpu.wait(refs, num_returns=4, timeout=30)
        assert len(ready) == 4 and not not_ready


class TestClusterInfo:
    def test_nodes(self, ray_start_regular):
        nodes = ray_tpu.nodes()
        assert len(nodes) == 1
        assert nodes[0]["alive"]

    def test_cluster_resources(self, ray_start_regular):
        res = ray_tpu.cluster_resources()
        assert res["CPU"] == 4.0

    def test_runtime_context(self, ray_start_regular):
        ctx = ray_tpu.get_runtime_context()
        assert ctx.get_job_id()
        assert ctx.get_node_id()

        @ray_tpu.remote
        def get_ctx():
            c = ray_tpu.get_runtime_context()
            return (c.get_task_id(), c.get_task_name())

        task_id, name = ray_tpu.get(get_ctx.remote())
        assert task_id is not None
        assert "get_ctx" in name

    def test_timeline_events(self, ray_start_regular):
        ray_tpu.get(add.remote(1, 1))
        events = ray_tpu.timeline()
        assert isinstance(events, list)
