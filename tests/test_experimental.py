"""Tests for experimental utils, tracing/timeline, Pool, joblib, parallel
iterators (reference parity: python/ray/tests/test_multiprocessing.py,
test_joblib.py, test_iter.py, experimental tests)."""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray4():
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


class TestInternalKV:
    def test_roundtrip(self, ray4):
        from ray_tpu.experimental import internal_kv as kv

        assert kv._internal_kv_put(b"tk", b"tv")
        assert kv._internal_kv_get(b"tk") == b"tv"
        assert kv._internal_kv_exists(b"tk")
        assert b"tk" in kv._internal_kv_list(b"t")
        kv._internal_kv_del(b"tk")
        assert not kv._internal_kv_exists(b"tk")

    def test_no_overwrite(self, ray4):
        from ray_tpu.experimental import internal_kv as kv

        kv._internal_kv_put(b"now", b"first")
        assert not kv._internal_kv_put(b"now", b"second", overwrite=False)
        assert kv._internal_kv_get(b"now") == b"first"


class TestChannel:
    def test_spsc_roundtrip(self, ray4):
        from ray_tpu.experimental.channel import Channel

        ch = Channel(capacity=2)

        @ray_tpu.remote
        def producer(ch, n):
            for i in range(n):
                ch.write(np.full((100,), i, np.float32))
            return "done"

        ref = producer.remote(ch, 6)
        for i in range(6):
            arr = ch.read(timeout=60)
            assert arr[0] == i
        assert ray_tpu.get(ref, timeout=60) == "done"

    def test_backpressure_capacity(self, ray4):
        from ray_tpu.experimental.channel import Channel

        ch = Channel(capacity=1)
        ch.write(1)
        with pytest.raises(TimeoutError):
            ch.write(2, timeout=0.3)  # reader never consumed slot 0
        assert ch.read(timeout=5) == 1
        ch.write(2)  # now fits
        assert ch.read(timeout=5) == 2


class TestTimelineTracing:
    def test_timeline_complete_events(self, ray4):
        @ray_tpu.remote
        def quick():
            return 1

        ray_tpu.get([quick.remote() for _ in range(3)], timeout=60)
        tl = ray_tpu.timeline()
        xs = [e for e in tl
              if e["ph"] == "X" and "quick" in (e.get("name") or "")]
        assert xs, "no complete task events"
        assert all(e["dur"] >= 0 for e in xs)


class TestPool:
    def test_map_and_apply(self, ray4):
        from ray_tpu.util.multiprocessing import Pool

        with Pool(processes=2) as pool:
            assert pool.map(lambda x: x * x, range(10)) == \
                [x * x for x in range(10)]
            assert pool.apply(lambda a, b: a + b, (3, 4)) == 7
            assert sorted(pool.imap_unordered(lambda x: -x, range(5))) == \
                [-4, -3, -2, -1, 0]
            assert pool.starmap(lambda a, b: a * b, [(2, 3), (4, 5)]) == \
                [6, 20]

    def test_async_results(self, ray4):
        from ray_tpu.util.multiprocessing import Pool

        with Pool(processes=2) as pool:
            res = pool.map_async(lambda x: x + 1, range(6))
            assert res.get(timeout=60) == list(range(1, 7))
            assert res.successful()


class TestJoblib:
    def test_parallel_backend(self, ray4):
        joblib = pytest.importorskip("joblib")
        from ray_tpu.util.joblib import register_ray

        register_ray()
        with joblib.parallel_backend("ray", n_jobs=2):
            out = joblib.Parallel()(
                joblib.delayed(lambda x: x ** 2)(i) for i in range(8))
        assert out == [i ** 2 for i in range(8)]


class TestParallelIterator:
    def test_for_each_filter_gather(self, ray4):
        from ray_tpu.util import iter as rt_iter

        it = (rt_iter.from_range(20, num_shards=3)
              .for_each(lambda x: x * 2)
              .filter(lambda x: x % 4 == 0))
        out = sorted(it.gather_sync())
        assert out == sorted(x * 2 for x in range(20) if (x * 2) % 4 == 0)

    def test_batch(self, ray4):
        from ray_tpu.util import iter as rt_iter

        batches = list(rt_iter.from_range(10, num_shards=2).batch(3))
        flat = [x for b in batches for x in b]
        assert sorted(flat) == list(range(10))
        assert all(len(b) <= 3 for b in batches)
