"""Serving-plane load tests (ISSUE 6): bounded admission queues + typed
BackPressureError shed, continuous-batching engine join/leave correctness,
queue-depth autoscaling up/drain-down, replica-kill-mid-stream, and the
@serve.batch per-instance queue keying (weak, no id-reuse mixing).

Reference analog: python/ray/serve/tests/test_backpressure.py +
test_autoscaling_policy.py, scaled to the in-repo control plane.
"""

import gc
import os
import signal
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.exceptions import BackPressureError
from ray_tpu.serve._private.engine import ContinuousBatchingEngine


# ---------------------------------------------------------------------------
# Engine unit tests (no cluster)
# ---------------------------------------------------------------------------
def _mk_prefill():
    def prefill(payload, model_id):
        return {"tag": payload["tag"], "n": int(payload["n"]), "i": 0}

    return prefill


def _mk_step(delay=0.0, gate=None, seen=None):
    def step(model_id, states):
        if gate is not None:
            gate.wait(timeout=30)
        if delay:
            time.sleep(delay)
        if seen is not None:
            seen.append((model_id,
                         sum(1 for s in states if s is not None),
                         len(states)))
        results = [None] * len(states)
        for i, s in enumerate(states):
            if s is None:
                continue
            s["i"] += 1
            results[i] = (f"{s['tag']}{s['i']}", s["i"] >= s["n"])
        return results

    return step


def _collect(engine, payload, model_id="", out=None, idx=None):
    toks = list(engine.submit(payload, model_id))
    if out is not None:
        out[idx] = toks
    return toks


def test_engine_single_request():
    eng = ContinuousBatchingEngine(
        _mk_step(), prefill_fn=_mk_prefill(), max_batch_size=4,
        idle_timeout_s=0.1, name="single")
    assert _collect(eng, {"tag": "a", "n": 3}) == ["a1", "a2", "a3"]
    eng.shutdown()


def test_engine_join_leave_interleaved():
    """Short generations join a running batch at step boundaries and leave
    when done — they must NOT wait for the long one, and every request
    gets exactly its own tokens."""
    eng = ContinuousBatchingEngine(
        _mk_step(delay=0.01), prefill_fn=_mk_prefill(), max_batch_size=4,
        idle_timeout_s=0.2, name="interleave")
    done_at = {}
    out = {}

    def run(idx, tag, n):
        out[idx] = list(eng.submit({"tag": tag, "n": n}))
        done_at[idx] = time.monotonic()

    threads = [threading.Thread(target=run, args=(0, "L", 40))]
    threads[0].start()
    time.sleep(0.05)  # long one is mid-flight; shorts join its batch
    for i, tag in ((1, "s"), (2, "t"), (3, "u")):
        threads.append(threading.Thread(target=run, args=(i, tag, 3)))
        threads[-1].start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "engine request hung"
    assert out[0] == [f"L{i}" for i in range(1, 41)]
    for i, tag in ((1, "s"), (2, "t"), (3, "u")):
        assert out[i] == [f"{tag}1", f"{tag}2", f"{tag}3"]
        assert done_at[i] < done_at[0], \
            "short generation waited for the long one (no iteration-level " \
            "leave)"
    stats = eng.stats()
    assert stats["max_batch"] > 1, "requests never shared a batch"
    assert stats["completed"] == 4
    eng.shutdown()


def test_engine_bucketed_batch_sizes():
    seen = []
    eng = ContinuousBatchingEngine(
        _mk_step(seen=seen), prefill_fn=_mk_prefill(), max_batch_size=4,
        allowed_batch_sizes=(2, 4), idle_timeout_s=0.2, name="buckets")
    assert eng.bucket_for(1) == 2
    assert eng.bucket_for(3) == 4
    assert eng.bucket_for(4) == 4
    out = {}
    threads = [threading.Thread(target=_collect,
                                args=(eng, {"tag": f"r{i}", "n": 6}, "",
                                      out, i))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    for i in range(3):
        assert out[i] == [f"r{i}{j}" for j in range(1, 7)]
    # every dispatched step was padded to an allowed bucket
    assert seen, "no steps recorded"
    for _mid, _live, padded in seen:
        assert padded in (2, 4), f"step ran at non-bucket width {padded}"
    assert eng.stats()["padded_slots"] > 0
    eng.shutdown()


def test_engine_multi_adapter_grouping():
    """Multiplexed requests are grouped per adapter: every step runs a
    single model_id, and all adapters make progress (round-robin)."""
    seen = []
    eng = ContinuousBatchingEngine(
        _mk_step(seen=seen), prefill_fn=_mk_prefill(), max_batch_size=4,
        idle_timeout_s=0.2, name="adapters")
    out = {}
    threads = []
    for i in range(4):
        mid = f"adapter-{i % 2}"
        t = threading.Thread(target=_collect,
                             args=(eng, {"tag": f"x{i}", "n": 5}, mid,
                                   out, i))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=60)
    for i in range(4):
        assert out[i] == [f"x{i}{j}" for j in range(1, 6)]
    mids = {m for m, _, _ in seen}
    assert mids == {"adapter-0", "adapter-1"}, f"adapters seen: {mids}"
    eng.shutdown()


def test_engine_backpressure_shed():
    gate = threading.Event()
    eng = ContinuousBatchingEngine(
        _mk_step(gate=gate), prefill_fn=_mk_prefill(), max_batch_size=2,
        max_pending=2, idle_timeout_s=0.2, name="shed")
    out = {}
    threads = [threading.Thread(target=_collect,
                                args=(eng, {"tag": f"b{i}", "n": 2}, "",
                                      out, i))
               for i in range(2)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 10
    while eng.stats()["running"] + eng.stats()["pending"] < 2 and \
            time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(BackPressureError) as ei:
        eng.submit({"tag": "nope", "n": 1})
    assert eng.stats()["shed"] == 1
    assert ei.value.queue_depths  # carries the observed depth
    gate.set()
    for t in threads:
        t.join(timeout=30)
    assert out[0] == ["b01", "b02"] and out[1] == ["b11", "b12"]
    eng.shutdown()


def test_engine_step_error_propagates():
    def bad_step(model_id, states):
        raise ValueError("boom in step")

    eng = ContinuousBatchingEngine(
        bad_step, prefill_fn=_mk_prefill(), idle_timeout_s=0.1, name="err")
    with pytest.raises(ValueError, match="boom in step"):
        list(eng.submit({"tag": "z", "n": 2}))
    eng.shutdown()


def test_engine_shutdown_mid_generation_no_hang():
    eng = ContinuousBatchingEngine(
        _mk_step(delay=0.02), prefill_fn=_mk_prefill(),
        idle_timeout_s=0.2, name="mid-shutdown")
    caught = {}

    def run():
        try:
            list(eng.submit({"tag": "w", "n": 10_000}))
        except RuntimeError as e:
            caught["err"] = e

    t = threading.Thread(target=run)
    t.start()
    time.sleep(0.15)  # generation is mid-flight
    eng.shutdown()
    t.join(timeout=30)
    assert not t.is_alive(), "consumer hung through engine shutdown"
    assert "shut down" in str(caught.get("err"))


def test_engine_idle_stepper_exits():
    """The background stepper must not outlive its work: an idle engine
    leaves no thread behind (this is what the conftest leak gate checks
    at session end)."""
    from ray_tpu.serve._private.engine import live_stepper_threads

    eng = ContinuousBatchingEngine(
        _mk_step(), prefill_fn=_mk_prefill(), idle_timeout_s=0.1,
        name="idle-exit")
    assert _collect(eng, {"tag": "q", "n": 2}) == ["q1", "q2"]
    deadline = time.monotonic() + 5
    while any("idle-exit" in n for n in live_stepper_threads()) and \
            time.monotonic() < deadline:
        time.sleep(0.05)
    assert not any("idle-exit" in n for n in live_stepper_threads()), \
        "stepper thread survived past idle_timeout_s"
    # and it restarts lazily for new work
    assert _collect(eng, {"tag": "r", "n": 1}) == ["r1"]
    eng.shutdown()


# ---------------------------------------------------------------------------
# What the engine says it was doing (ISSUE 38): counters in stats(), and
# the same intervals as spans in the flight recorder's ring
# ---------------------------------------------------------------------------
def _wait_until(pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.002)


def test_engine_counts_queue_wait_and_active_time():
    """A batch of 2 and a third request: its wait for a row is counted
    (at least one step), the iterations' time lies between the steps' own
    sleeps and the wall time, and stats() is a flat dict of numbers."""
    delay = 0.05
    eng = ContinuousBatchingEngine(
        _mk_step(delay=delay), prefill_fn=_mk_prefill(), max_batch_size=2,
        idle_timeout_s=0.2, name="waits")
    t0 = time.perf_counter()
    out = {}
    threads = [threading.Thread(target=_collect,
                                args=(eng, {"tag": f"r{i}", "n": n}, "",
                                      out, i))
               for i, n in enumerate((8, 8, 2))]
    for t in threads[:2]:
        t.start()
    _wait_until(lambda: eng.stats()["running"] == 2)
    threads[2].start()  # both rows are held for several more steps
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "engine request hung"
    # a request is done before the iteration that finished it is summed:
    # read once the stepper has found nothing more to do and gone
    _wait_until(lambda: eng._thread is None)
    wall = time.perf_counter() - t0
    stats = eng.stats()
    eng.shutdown()
    assert [len(out[i]) for i in range(3)] == [8, 8, 2]
    assert stats["joined"] == 3 and stats["completed"] == 3
    assert stats["queue_wait_max_s"] >= delay, stats
    assert stats["queue_wait_s"] >= stats["queue_wait_max_s"]
    assert stats["queue_wait_s"] < wall
    assert stats["steps"] * delay <= stats["active_s"] <= wall, (stats, wall)
    for name, value in stats.items():
        assert isinstance(value, (int, float)) \
            and not isinstance(value, bool), (name, value)


def test_engine_cancelled_before_joining_counts_no_wait():
    gate = threading.Event()
    eng = ContinuousBatchingEngine(
        _mk_step(gate=gate), prefill_fn=_mk_prefill(), max_batch_size=1,
        idle_timeout_s=0.2, name="cancelled")
    first = threading.Thread(target=_collect,
                             args=(eng, {"tag": "a", "n": 2}))
    first.start()
    _wait_until(lambda: eng.stats()["running"] == 1)
    eng.submit({"tag": "b", "n": 2})  # the one row is held: it waits
    with eng._lock:
        assert len(eng._pending) == 1
        eng._pending[0].cancelled = True  # its consumer went away
    time.sleep(0.05)
    gate.set()
    first.join(timeout=60)
    assert not first.is_alive()
    _wait_until(lambda: eng.stats()["pending"] == 0)
    stats = eng.stats()
    eng.shutdown()
    # it waited 50 ms and more: counted, it would be a second join and
    # the sum would pass the longest
    assert stats["joined"] == 1 and stats["completed"] == 1
    assert stats["queue_wait_s"] == stats["queue_wait_max_s"]


def test_engine_spans_in_the_flight_recorder(armed_recorder):
    """Armed at 1.0, one request is one trace (``request.queue`` then
    ``request.generate``), and an iteration is three sibling spans that
    share a trace, name no parent, carry the batch in their extra and do
    not overlap on the ring's clock."""
    from ray_tpu._private.events import _span_dict

    eng = ContinuousBatchingEngine(
        _mk_step(delay=0.01), prefill_fn=_mk_prefill(), max_batch_size=2,
        allowed_batch_sizes=(2,), idle_timeout_s=0.2, name="recorded")
    assert _collect(eng, {"tag": "a", "n": 3}) == ["a1", "a2", "a3"]
    eng.shutdown()
    spans = [_span_dict(t) for t in armed_recorder.drain()]
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp)
    assert sorted(by_name) == ["engine.admit", "engine.emit",
                               "engine.step_fn", "request.generate",
                               "request.queue"], sorted(by_name)
    (queue,), (generate,) = by_name["request.queue"], \
        by_name["request.generate"]
    assert queue["trace"] == generate["trace"]
    assert queue["extra"] == {"emitted": 0}
    assert generate["extra"] == {"emitted": 3}
    assert queue["ts_us"] + queue["dur_us"] <= generate["ts_us"] + 1000
    assert generate["dur_us"] >= 3 * 10_000
    assert len(by_name["engine.step_fn"]) == 3
    for step in by_name["engine.step_fn"]:
        admit, = [s for s in by_name["engine.admit"]
                  if s["trace"] == step["trace"]]
        emit, = [s for s in by_name["engine.emit"]
                 if s["trace"] == step["trace"]]
        assert step["trace"] != queue["trace"]
        for sp in (admit, step, emit):
            assert sp["parent"] == 0 and sp["cat"] == "serve"
            assert sp["extra"] == {"rows": 1, "bucket": 2, "pad": 1,
                                   "pending": 0}
        assert admit["ts_us"] <= step["ts_us"] <= emit["ts_us"]
        assert step["dur_us"] >= 10_000


def test_span_helper_nests_under_the_enclosing_span(armed_recorder):
    """With no trace given a span is a child of the span that encloses it
    on its thread (how ``llm.prepare``, ``llm.device`` and ``llm.finish``
    come under ``engine.step_fn``), and outside any it records nothing."""
    from ray_tpu._private import events

    with events.span("alone", "serve"):
        pass
    assert armed_recorder.drain() == []
    with events.span("outer", "serve", trace=events.sampled_root()) as outer:
        with events.span("inner", "serve", {"k": 1}) as inner:
            time.sleep(0.002)
    assert inner.t0 >= outer.t0 and inner.t1 <= outer.t1
    got = {t[3]: events._span_dict(t) for t in armed_recorder.drain()}
    assert got["inner"]["trace"] == got["outer"]["trace"]
    assert got["inner"]["parent"] == got["outer"]["span"]
    assert got["outer"]["parent"] == 0
    assert got["inner"]["extra"] == {"k": 1}
    assert got["inner"]["dur_us"] >= 2000


def test_engine_serves_in_a_process_that_never_imports_jax():
    """``engine.py`` and the span helper import no jax: a process without
    it serves through the engine, spans and all."""
    import subprocess
    import sys

    body = (
        "import sys\n"
        "from ray_tpu.serve._private.engine import "
        "ContinuousBatchingEngine\n"
        "def step(model_id, states):\n"
        "    return [None if s is None else (s, True) for s in states]\n"
        "eng = ContinuousBatchingEngine(step, max_batch_size=2, "
        "idle_timeout_s=0.1)\n"
        "out = [list(eng.submit(i)) for i in range(3)]\n"
        "stats = eng.stats()\n"
        "eng.shutdown()\n"
        "assert out == [[0], [1], [2]], out\n"
        "assert stats['joined'] == 3 and stats['steps'] == 3, stats\n"
        "assert stats['active_s'] > 0, stats\n"
        "assert 'jax' not in sys.modules, 'the engine imported jax'\n"
        "print('served without jax')\n")
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
           if p])}
    done = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    assert "served without jax" in done.stdout


# ---------------------------------------------------------------------------
# @serve.batch per-instance queue keying (satellite: WeakKeyDictionary)
# ---------------------------------------------------------------------------
def test_batch_queues_not_shared_across_instances():
    import asyncio

    class Tagged:
        def __init__(self, tag):
            self.tag = tag

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.02)
        async def predict(self, items):
            return [f"{self.tag}:{it}" for it in items]

    async def go():
        a, b = Tagged("A"), Tagged("B")
        results = await asyncio.gather(
            *[a.predict(i) for i in range(4)],
            *[b.predict(i) for i in range(4)])
        return results

    results = asyncio.run(go())
    assert results[:4] == [f"A:{i}" for i in range(4)]
    assert results[4:] == [f"B:{i}" for i in range(4)]


def test_batch_queue_evicted_on_gc():
    """id(owner) keying never evicted → a GC'd instance's reused id could
    mix two instances' batches; weak keying evicts with the owner."""
    import asyncio

    from ray_tpu.serve.batching import _owner_queues

    class M:
        @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.01)
        async def f(self, items):
            return items

    m = M()
    assert asyncio.run(m.f(7)) == 7
    assert any(k is m for k in list(_owner_queues.keys()))
    del m
    gc.collect()
    assert not any(isinstance(k, M) for k in list(_owner_queues.keys())), \
        "batch queue kept its dead owner alive / was never evicted"


def test_batch_decorated_class_is_cloudpickleable():
    """Deployment classes travel to replicas via cloudpickle; the batching
    machinery must not hide unpicklable state in the wrapper."""
    import asyncio

    import cloudpickle

    class P:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.01)
        async def f(self, items):
            return [i + 1 for i in items]

    P2 = cloudpickle.loads(cloudpickle.dumps(P))

    async def go():
        p = P2()
        return await asyncio.gather(*[p.f(i) for i in range(3)])

    assert asyncio.run(go()) == [1, 2, 3]


# ---------------------------------------------------------------------------
# Cluster tests: admission queues, autoscaling, chaos
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def serve_cluster():
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=8)
    serve.start(http_options={"port": 0})
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_admission_queue_and_typed_shed(serve_cluster):
    """1 executing + 2 queued fit; everything beyond sheds with a typed
    BackPressureError (no spin-retry, no unbounded queue)."""

    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      max_queued_requests=2)
    class Slow:
        def __call__(self, x):
            time.sleep(2.0)
            return x * 2

    handle = serve.run(Slow.bind(), name="slow", route_prefix="/slow")
    t0 = time.monotonic()
    responses = [handle.remote(i) for i in range(6)]
    ok, shed = [], []
    for r in responses:
        try:
            ok.append(r.result(timeout_s=60))
        except BackPressureError as e:
            shed.append(e)
            # sheds must be FAST typed errors, not spin-retries burning
            # the deadline
            assert time.monotonic() - t0 < 30
    assert len(ok) == 3, f"admitted {len(ok)} (want 1 running + 2 queued)"
    assert len(shed) == 3
    assert all(v in {i * 2 for i in range(6)} for v in ok)
    # the controller saw the sheds through the health-probe piggyback
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        st = serve.status("slow")["deployments"].get("Slow", {})
        if st.get("shed_total", 0) >= 3:
            break
        time.sleep(0.25)
    assert st.get("shed_total", 0) >= 3, f"sheds not in status: {st}"
    serve.delete("slow")


def test_queue_drains_in_fifo_order(serve_cluster):
    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      max_queued_requests=8)
    class Seq:
        def __init__(self):
            self.order = []

        def __call__(self, x):
            self.order.append(x)
            time.sleep(0.05)
            return x

        def get_order(self):
            return self.order

    handle = serve.run(Seq.bind(), name="seq", route_prefix="/seq")
    # warm the path, then submit a strictly ordered burst
    handle.remote(-1).result(timeout_s=30)
    responses = []
    for i in range(6):
        responses.append(handle.remote(i))
        time.sleep(0.01)  # give each submit its admission turn
    assert [r.result(timeout_s=60) for r in responses] == list(range(6))
    order = serve.get_deployment_handle(
        "Seq", "seq").get_order.remote().result(timeout_s=30)
    assert order[1:] == sorted(order[1:]), \
        f"queued requests executed out of FIFO order: {order}"
    serve.delete("seq")


def test_autoscale_up_then_drain_down(serve_cluster):
    """Queue-depth-driven autoscaling: sustained load scales past 1
    replica; when the load stops the deployment drains back to
    min_replicas via Replica.drain."""

    @serve.deployment(max_ongoing_requests=2, max_queued_requests=64,
                      autoscaling_config={"min_replicas": 1,
                                          "max_replicas": 3,
                                          "target_ongoing_requests": 1.0,
                                          "upscale_delay_s": 0.5,
                                          "downscale_delay_s": 0.5})
    class Busy:
        def __call__(self, x):
            time.sleep(0.25)
            return x

    handle = serve.run(Busy.bind(), name="busy", route_prefix="/busy")
    stop = threading.Event()
    errors = []

    def client():
        while not stop.is_set():
            try:
                handle.remote(1).result(timeout_s=60)
            except BackPressureError:
                pass  # overload shed is allowed; hangs/other errors not
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(6)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 60
        peak = 1
        while time.monotonic() < deadline:
            st = serve.status("busy")["deployments"].get("Busy", {})
            peak = max(peak, st.get("replicas", 1))
            if peak > 1:
                break
            time.sleep(0.25)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    assert not errors, f"client saw non-backpressure errors: {errors[:3]}"
    assert peak > 1, "deployment never scaled up under sustained load"
    # drain back down to min_replicas
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        st = serve.status("busy")["deployments"].get("Busy", {})
        if st.get("replicas") == 1 and st.get("target_replicas") == 1:
            break
        time.sleep(0.5)
    assert st.get("replicas") == 1, f"did not drain to min_replicas: {st}"
    serve.delete("busy")


def test_replica_kill_mid_stream_typed_error(serve_cluster):
    """SIGKILL the replica mid-stream: the consumer gets a clean typed
    error (or the stream completes via another replica) — never a hang;
    the deployment recovers for subsequent requests."""

    @serve.deployment(num_replicas=1, max_ongoing_requests=4)
    class Streamer:
        def pid(self):
            return os.getpid()

        def __call__(self, n):
            for i in range(int(n)):
                time.sleep(0.1)
                yield i

    handle = serve.run(Streamer.bind(), name="streamer",
                       route_prefix="/streamer")
    victim = handle.pid.remote().result(timeout_s=30)
    outcome = {}
    got: list = []

    def consume():
        try:
            for chunk in handle.options(stream=True).remote(100):
                got.append(chunk)
        except Exception as e:  # noqa: BLE001 — asserted typed below
            outcome["error"] = e

    t = threading.Thread(target=consume)
    t.start()
    deadline = time.monotonic() + 30
    while not got and time.monotonic() < deadline:
        time.sleep(0.05)  # wait until the stream is flowing
    os.kill(victim, signal.SIGKILL)
    t.join(timeout=60)
    assert not t.is_alive(), "stream consumer hung after replica kill"
    err = outcome.get("error")
    if err is not None:
        from ray_tpu.exceptions import RayTpuError

        assert isinstance(err, (RayTpuError, ConnectionError)), \
            f"untyped error after replica kill: {type(err).__name__}: {err}"
    # the controller replaces the dead replica; new requests succeed
    deadline = time.monotonic() + 90
    recovered = False
    while time.monotonic() < deadline and not recovered:
        try:
            got = list(handle.options(stream=True).remote(3))
            recovered = got == [0, 1, 2]
        except Exception:  # noqa: BLE001 — still recovering
            time.sleep(0.5)
    assert recovered, "deployment did not recover after replica kill"
    serve.delete("streamer")


def test_llama_engine_generation():
    """llm.py wiring: continuously-batched LoRA generation produces the
    right number of tokens per request and distinct adapters generate
    distinct sequences (in-process, no cluster — replica hosting is
    covered by the cluster tests above)."""
    from ray_tpu.serve.llm import LlamaGenerator

    gen = LlamaGenerator(config="debug_1l", lora_rank=2,
                         max_batch_size=2, allowed_batch_sizes=(1, 2),
                         max_new_tokens=4, seq_bucket=16)
    try:
        out = {}
        threads = []
        for i, adapter in enumerate(("", "a1", "a2", "a1")):
            def run(idx=i, ad=adapter):
                out[idx] = list(gen({"prompt": [3, 5, 7], "max_new": 4,
                                     "adapter": ad}))

            t = threading.Thread(target=run)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive(), "llama generation hung"
        for i in range(4):
            assert len(out[i]) == 4, f"request {i}: {out[i]}"
            assert all(isinstance(t, int) for t in out[i])
        # same adapter + same prompt → identical (greedy); the two a1
        # requests joined different batches, so this also checks padding
        # doesn't leak across rows
        assert out[1] == out[3], "same adapter diverged across batches"
        stats = gen.engine.stats()
        assert stats["completed"] == 4
    finally:
        gen.engine.shutdown()


def _llama_gen(allowed=(1, 2), seq_bucket=8):
    from ray_tpu.serve.llm import LlamaGenerator

    return LlamaGenerator(config="debug_1l", lora_rank=2,
                          max_batch_size=max(allowed),
                          allowed_batch_sizes=allowed,
                          max_new_tokens=4, seq_bucket=seq_bucket)


@pytest.mark.parametrize("bucket", [1, 2])
@pytest.mark.parametrize("adapter", ["", "a1"])
def test_llama_step_ids_match_full_logits(adapter, bucket):
    """The ids ``_step`` emits (last-position head and argmax on the
    device) are the argmax of the full-logits call ``_fwd`` at each row's
    last position on the same padded tokens: the path a benchmark checks
    against its reference and the path it times are one computation.
    Rows of unequal length share the batch, the longer one crosses a
    sequence bucket, and the shorter one leaves a padded row behind."""
    import jax.numpy as jnp
    import numpy as np

    gen = _llama_gen()
    try:
        prompts = [[9, 8, 7, 6, 5, 4, 3], [3, 5, 7]][:bucket]
        states = [gen._prefill({"prompt": p, "max_new": 4 - 2 * i}, adapter)
                  for i, p in enumerate(prompts)]
        pad_lens = set()
        for _ in range(4):
            before = [None if s is None else list(s["tokens"])
                      for s in states]
            out = gen._step(adapter, states)
            pad_len = -(-max(len(t) for t in before if t) // 8) * 8
            pad_lens.add(pad_len)
            tokens = np.zeros((bucket, pad_len), np.int32)
            live = [t for t in before if t is not None]
            for row, t in enumerate(live):
                tokens[row, :len(t)] = t
            logits = np.asarray(gen._fwd(gen._params, jnp.asarray(tokens),
                                         gen._adapter(adapter)))
            assert logits.shape == (bucket, pad_len, 128)
            assert logits.dtype == np.float32
            want = [int(np.argmax(logits[row, len(t) - 1]))
                    for row, t in enumerate(live)]
            assert [r[0] for r in out if r is not None] == want
            # a finished request leaves, as the engine has it
            states = [None if r is None or r[1] else s
                      for s, r in zip(states, out)]
        assert pad_lens == {8, 16}
        assert states[0] is None
        assert gen.engine_stats()["host_bytes"] == 4 * bucket * 4
    finally:
        gen.engine.shutdown()


def test_llama_host_bytes_counts_ids_only():
    """What a step brings to the host is one int32 a row of the bucket."""
    gen = _llama_gen(allowed=(2,))
    try:
        assert gen.engine_stats()["host_bytes"] == 0
        out = list(gen({"prompt": [3, 5, 7], "max_new": 4}))
        assert len(out) == 4
        stats = gen.engine_stats()
        assert stats["steps"] == 4
        assert stats["host_bytes"] == stats["steps"] * 2 * 4
    finally:
        gen.engine.shutdown()


def test_llama_step_compiles_nothing_after_its_shape_was_warmed():
    """A caller that warms a (batch, seq) shape as a benchmark does
    (``warm_step_programs``: the step's program handed a mask, which every
    model's step is since PR 56; ``_fwd``'s, with none, is another) has
    compiled everything ``_step`` runs at that shape: no backend
    compilation fires in the step, by the count a benchmark run is
    failed on (``compiles_in_window``)."""
    from benchmark.harness.onchip import count_compiles

    compiles = count_compiles()
    gen = _llama_gen(allowed=(2,), seq_bucket=24)
    try:
        gen.warm_step_programs(24)
        assert compiles, "the listener saw the warm-up compile nothing"
        warmed = len(compiles)
        states = [gen._prefill({"prompt": [3, 5, 7]}, ""),
                  gen._prefill({"prompt": list(range(1, 12))}, "")]
        for _ in range(2):
            out = gen._step("", states)
            assert all(0 <= tok < 128 for tok, _ in out)
        assert len(compiles) == warmed, (
            f"{len(compiles) - warmed} compilation(s) in a warmed step")
    finally:
        gen.engine.shutdown()


def _host_events(path, prefix):
    """(name, start_ns, end_ns, plane, line) of every event on a trace's
    planes whose name starts with ``prefix``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                plane.name, line.name))
    return sorted(out, key=lambda e: e[1])


def test_llama_spans_lie_in_the_profilers_trace(tmp_path):
    """Under ``jax.profiler.start_trace`` the program's spans are in the
    ``.xplane.pb`` beside whatever else the process did, on the
    profiler's clock: every iteration is ``ray_tpu:engine.admit``,
    ``ray_tpu:engine.step_fn``, ``ray_tpu:engine.emit`` in that order
    without overlap, and each ``engine.step_fn`` holds
    ``ray_tpu:llm.prepare``, ``ray_tpu:llm.device`` and
    ``ray_tpu:llm.finish``, likewise. The device's half of a step is
    inside the engine's iterations."""
    import glob

    import jax

    gen = _llama_gen(allowed=(2,))
    try:
        list(gen({"prompt": [3, 5, 7], "max_new": 2}))  # compiles
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("test:requests"):
                out = list(gen({"prompt": [3, 5, 7, 9], "max_new": 4}))
        finally:
            jax.profiler.stop_trace()
        assert len(out) == 4
        stats = gen.engine_stats()
    finally:
        gen.engine.shutdown()
    assert 0 < stats["step_device_s"] <= stats["active_s"], stats
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert _host_events(path, "test:requests"), "the trace holds no span"
    spans = _host_events(path, "ray_tpu:")
    lines = {e[3:] for e in spans}
    assert len(lines) == 1, f"the stepper is one thread: {lines}"
    engine = [e for e in spans if e[0].startswith("ray_tpu:engine.")]
    steps = [e for e in engine if e[0] == "ray_tpu:engine.step_fn"]
    assert len(steps) == 4, [e[0] for e in engine]
    want = ["ray_tpu:engine.admit", "ray_tpu:engine.step_fn",
            "ray_tpu:engine.emit"]
    # a pass that found every request gone ends in `engine.admit` alone
    names = [e[0] for e in engine]
    first = names.index("ray_tpu:engine.step_fn") - 1
    assert names[first:first + 12] == want * 4, names
    for (_, _, end, *_), (_, start, *_) in zip(engine, engine[1:]):
        assert start >= end, "two of the engine's spans overlap"
    inside = ["ray_tpu:llm.prepare", "ray_tpu:llm.device",
              "ray_tpu:llm.finish"]
    for _, s0, s1, *_ in steps:
        children = [e for e in spans if e[0].startswith("ray_tpu:llm.")
                    and s0 <= e[1] and e[2] <= s1]
        assert [e[0] for e in children] == inside, children
        for (_, _, end, *_), (_, start, *_) in zip(children, children[1:]):
            assert start >= end, "two of a step's spans overlap"
    assert len([e for e in spans if e[0].startswith("ray_tpu:llm.")]) == 12
