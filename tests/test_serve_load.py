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


def test_llama_step_compiles_nothing_after_fwd_warmed_its_shape():
    """A caller that warms a (batch, seq) shape through ``_fwd`` has
    compiled everything ``_step`` runs at that shape: no backend
    compilation fires in the step, by the count a benchmark run is
    failed on (``compiles_in_window``)."""
    import jax.numpy as jnp

    from benchmark.harness.onchip import count_compiles

    compiles = count_compiles()
    gen = _llama_gen(allowed=(2,), seq_bucket=24)
    try:
        gen._fwd(gen._params, jnp.zeros((2, 24), jnp.int32), None)
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
