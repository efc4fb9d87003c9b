"""Core-runtime microbenchmarks (reference: python/ray/_private/ray_perf.py:93
— the suite behind the release microbenchmark numbers in BASELINE.md:
single-client sync/async tasks, 1:1 and n:n actor calls, put/get).

Run: ``python -m ray_tpu._private.ray_perf [--filter substr]``
Prints one line per benchmark: ``name: N ops/s`` plus a JSON summary.
"""

from __future__ import annotations

import json
import statistics
import time
from typing import Callable, Dict, List

import numpy as np


def timeit(name: str, fn: Callable[[], None], multiplier: int = 1,
           min_time_s: float = 2.0) -> float:
    """Run fn repeatedly for ~min_time_s; returns ops/s
    (reference: ray_perf.py timeit)."""
    # warmup
    fn()
    start = time.perf_counter()
    count = 0
    while time.perf_counter() - start < min_time_s:
        fn()
        count += 1
    took = time.perf_counter() - start
    rate = count * multiplier / took
    print(f"{name}: {rate:.1f} ops/s")
    return rate


def main(filter_substr: str = "") -> Dict[str, float]:
    import ray_tpu

    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=4)

    results: Dict[str, float] = {}

    def bench(name, fn, multiplier=1):
        if filter_substr and filter_substr not in name:
            return
        results[name] = timeit(name, fn, multiplier)

    # ---------------------------------------------------------------- tasks
    @ray_tpu.remote
    def noop():
        pass

    ray_tpu.get(noop.remote(), timeout=60)  # prime worker pool

    bench("single client tasks sync",
          lambda: ray_tpu.get(noop.remote()))

    # round sizes mirror the reference suite (reference ray_perf.py:204
    # submits 1000 per async round; :222-232 runs the n:n pattern through
    # m concurrent CLIENT worker processes) so the numbers are comparable
    # with BASELINE.md's
    N_ASYNC = 1000
    bench("single client tasks async",
          lambda: ray_tpu.get([noop.remote() for _ in range(N_ASYNC)]),
          multiplier=N_ASYNC)

    # vectorized submission (ISSUE 18): the same round submitted through
    # fn.map — one id block / registration batch / wire frame instead of
    # N_ASYNC driver round-trips. Also reports the driver-tax metric the
    # fast path is actually about: main-thread submit µs per call.
    @ray_tpu.remote
    def noop1(i):
        pass

    ray_tpu.get(noop1.remote(0), timeout=60)
    if not filter_substr or filter_substr in "single client tasks batched":
        submit_us: List[float] = []

        def batched_round():
            t0 = time.perf_counter()
            refs = noop1.map(range(N_ASYNC))
            submit_us.append((time.perf_counter() - t0) / N_ASYNC * 1e6)
            ray_tpu.get(refs)

        results["single client tasks batched"] = timeit(
            "single client tasks batched", batched_round,
            multiplier=N_ASYNC)
        med_submit = statistics.median(submit_us)
        print(f"single client tasks batched submit: "
              f"{med_submit:.1f} us/call (main thread)")
        results["single client tasks batched submit us"] = round(
            med_submit, 2)

    # ----------------------------------------------------------------- puts
    bench("single client put small",
          lambda: ray_tpu.put(b"x" * 100))

    arr = np.zeros((5 << 18,), np.float32)  # 5 MiB

    # hardware context for the put number: a put is bounded below by ONE
    # 5-MiB copy into the shm arena, so report this box's raw single-thread
    # copy bandwidth alongside (the reference's 19.45 GB/s figure came from
    # an m4.16xlarge with many memory channels)
    if not filter_substr or filter_substr in "raw memcpy gigabytes":
        dst = bytearray(arr.nbytes)
        src = memoryview(arr).cast("B")
        dst[:] = src
        t0 = time.perf_counter()
        reps = 0
        while time.perf_counter() - t0 < 1.0:
            dst[:] = src
            reps += 1
        mgbps = reps * arr.nbytes / (time.perf_counter() - t0) / 1e9
        print(f"raw memcpy gigabytes: {mgbps:.2f} GB/s")
        results["raw memcpy gigabytes"] = mgbps

    def put_large():
        for _ in range(10):
            ray_tpu.put(arr)

    t0 = time.perf_counter()
    if not filter_substr or filter_substr in "single client put gigabytes":
        n = 0
        while time.perf_counter() - t0 < 2.0:
            put_large()
            n += 1
        gbps = n * 10 * arr.nbytes / (time.perf_counter() - t0) / 1e9
        print(f"single client put gigabytes: {gbps:.2f} GB/s")
        results["single client put gigabytes"] = gbps

    ref = ray_tpu.put(arr)
    bench("single client get large",
          lambda: ray_tpu.get(ref))

    # multi client tasks async: m actor-clients each submit a batch of
    # noop TASKS from inside their own process (reference:
    # ray_perf.py:181-189 small_value_batch x4)
    N_MULTI, M_MULTI = 2500, 4

    @ray_tpu.remote
    class TaskClient:
        def submit_batch(self, n):
            ray_tpu.get([noop.remote() for _ in range(n)])

    # near-zero CPU: the clients must leave the pool's cores to the
    # tasks they submit (reference actors hold 0 CPU while alive)
    clients = [TaskClient.options(num_cpus=0.001).remote()
               for _ in range(M_MULTI)]
    for c in clients:
        ray_tpu.get(c.submit_batch.remote(2), timeout=120)
    bench("multi client tasks async",
          lambda: ray_tpu.get([c.submit_batch.remote(N_MULTI)
                               for c in clients], timeout=600),
          multiplier=N_MULTI * M_MULTI)
    for c in clients:
        ray_tpu.kill(c)

    # ---------------------------------------------------------------- actors
    @ray_tpu.remote
    class Actor:
        def noop(self):
            pass

    a = Actor.remote()
    ray_tpu.get(a.noop.remote(), timeout=60)
    bench("1:1 actor calls sync", lambda: ray_tpu.get(a.noop.remote()))
    bench("1:1 actor calls async",
          lambda: ray_tpu.get([a.noop.remote() for _ in range(N_ASYNC)]),
          multiplier=N_ASYNC)

    actors = [Actor.remote() for _ in range(4)]
    for act in actors:
        ray_tpu.get(act.noop.remote(), timeout=60)

    # n:n = n CLIENTS x n actors: m concurrent driver-side `work` tasks
    # each fan N_NN calls over the actor pool from their own worker
    # process (reference: ray_perf.py:222-232 — `work.remote(actors)` x m)
    N_NN, M_NN = 1000, 4

    @ray_tpu.remote
    def work(actor_handles):
        ray_tpu.get([actor_handles[i % len(actor_handles)].noop.remote()
                     for i in range(N_NN)])

    bench("n:n actor calls async",
          lambda: ray_tpu.get([work.remote(actors) for _ in range(M_NN)]),
          multiplier=N_NN * M_NN)
    for act in actors + [a]:
        ray_tpu.kill(act)

    # flight-recorder A/B (ISSUE 14): the same async-task bench with the
    # recorder OFF (the default this suite runs under) vs ON at sample
    # rate 1.0 — the honest cost of full span recording — plus the
    # measured disabled-guard cost, which is what the <2% hard
    # requirement is actually about (you cannot A/B the disabled path
    # against "no instrumentation at runtime"; the guard probe times the
    # exact branch every site pays)
    if not filter_substr or "events" in filter_substr:
        from ray_tpu._private import events as _ev

        @ray_tpu.remote
        def noop_ev():
            pass

        ray_tpu.get(noop_ev.remote(), timeout=60)

        def run_batch():
            ray_tpu.get([noop_ev.remote() for _ in range(N_ASYNC)])

        off_rate = timeit("tasks async (events off)", run_batch,
                          multiplier=N_ASYNC)
        w = ray_tpu._worker_mod.global_worker
        armed = _ev.configure(w.session_dir or "/tmp", w.mode,
                              sample_rate=1.0)
        on_rate = timeit("tasks async (events on)", run_batch,
                         multiplier=N_ASYNC)
        _ev.REC.enabled = False  # restore the suite's default
        results["events ab"] = {
            "off_tasks_per_s": round(off_rate, 1),
            "on_tasks_per_s": round(on_rate, 1),
            "on_overhead_pct": round(
                (off_rate - on_rate) / off_rate * 100, 2) if off_rate else 0,
            "recorder_armed": armed,
            "disabled_guard_ns": round(_ev.overhead_probe(100_000), 1),
        }
        print(json.dumps({"events ab": results["events ab"]}))

    # direct-call transport columns (ISSUE 11): which lane the actor
    # benches above actually rode — shm frame counts prove same-node
    # calls bypassed loopback TCP; fallback counters prove the ladder
    # engaged rather than dropping frames
    try:
        from ray_tpu._private.mux import MUX_STATS
        from ray_tpu._private.shm_rpc import stats_snapshot

        transport = {
            "mux_sessions_opened": MUX_STATS["sessions_opened"],
            "mux_streams_opened": MUX_STATS["streams_opened"],
            **{f"shm_{k}": v for k, v in stats_snapshot().items()},
        }
        print(json.dumps({"transport": transport}))
        results["transport"] = transport  # type: ignore[assignment]
    except Exception:
        pass

    print(json.dumps(results))
    return results


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--filter", default="")
    args = parser.parse_args()
    main(args.filter)
