"""A chip-holding process's start-up from inside (PR 57): the `startup.*`
spans and the book they fill (`events.startup_stats()`), jax's compile
events counted by phase and by what the persistent cache did with them
(`compile_cache.compile_stats()`), and the step that compiles
(`step_compiles`). Everything here is the CPU's: what is asserted is which
phases exist, what covers what and what is counted, never how long it took.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu._private import compile_cache, events

PHASES = ("startup.import_jax", "startup.devices", "startup.weights")


def book_now():
    """The book's seconds as they stand (it is the process's, and sums)."""
    return {k: v for k, v in events.startup_stats().items()
            if isinstance(v, float)}


def moved(before, name):
    return events.startup_stats().get(name, 0.0) - before.get(name, 0.0)


def replica_of(cls, *args, **kwargs):
    """The replica actor's own object round ``cls``, in this process."""
    import cloudpickle

    from ray_tpu.serve._private.replica import Replica

    return Replica(cloudpickle.dumps(cls), cloudpickle.dumps((args, kwargs)),
                   "app", "dep", 8, None)


def small_generator(**kwargs):
    from ray_tpu.serve.llm import LlamaGenerator

    return LlamaGenerator(config="debug_1l", lora_rank=2, max_batch_size=2,
                          allowed_batch_sizes=(2,), max_new_tokens=4,
                          seq_bucket=8, **kwargs)


def drained(rec):
    return [events._span_dict(t) for t in rec.drain()]


# ---------------------------------------------------------------------------
# the book
# ---------------------------------------------------------------------------
def test_the_phases_are_in_the_book_and_construct_covers_them():
    from ray_tpu.serve.llm import LlamaGenerator

    before = book_now()
    replica = replica_of(LlamaGenerator, "tiny")
    try:
        book = events.startup_stats()
        assert book is events.STARTUP
        for phase in PHASES + ("startup.construct",):
            assert phase in book, (phase, book)
        # a phase that ran has its first start beside its seconds
        for phase in PHASES[1:] + ("startup.construct",):
            assert 0 < book["at"][phase] <= time.time(), (phase, book)
        inside = sum(moved(before, phase) for phase in PHASES)
        assert moved(before, "startup.weights") > 0
        assert moved(before, "startup.devices") > 0
        assert moved(before, "startup.construct") >= inside
        # the test process had jax long before anybody asked
        assert moved(before, "startup.import_jax") == 0.0
    finally:
        replica._callable.engine.shutdown()


def test_the_first_import_of_a_module_is_a_phase_whoever_makes_it(
        tmp_path, monkeypatch):
    (tmp_path / "slow_module_57.py").write_text(
        "import time\ntime.sleep(0.05)\nVALUE = 57\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    events.time_first_import("slow_module_57", "import_slow")
    events.time_first_import("slow_module_57", "import_slow")
    hooks = [f for f in sys.meta_path if isinstance(f, events._FirstImport)]
    assert len(hooks) == 1, "asked twice, it is timed once"
    try:
        import slow_module_57
    finally:
        sys.modules.pop("slow_module_57", None)
    assert slow_module_57.VALUE == 57
    assert not any(isinstance(f, events._FirstImport) for f in sys.meta_path)
    assert 0.05 <= events.startup_stats()["startup.import_slow"] < 5.0
    # already imported: the phase reads 0.0 unless it was timed
    events.time_first_import("json", "import_json")
    assert events.startup_stats().pop("startup.import_json") == 0.0
    events.startup_stats().pop("startup.import_slow")
    events.startup_stats()["at"].pop("startup.import_slow")


def test_weights_ready_arrives_and_the_constructor_does_not_wait(monkeypatch):
    import jax

    gate, real = threading.Event(), jax.block_until_ready

    def held(tree):
        assert gate.wait(timeout=60), "nobody opened the gate"
        return real(tree)

    monkeypatch.setattr(jax, "block_until_ready", held)
    book = events.startup_stats()
    book.pop("weights_ready_s", None)
    before = book_now()
    gen = small_generator()
    try:
        # the constructor is back and the waiter still waits
        assert "weights_ready_s" not in book
        call_s = moved(before, "startup.weights")
        assert call_s > 0
        gate.set()
        deadline = time.time() + 30
        while "weights_ready_s" not in book and time.time() < deadline:
            time.sleep(0.01)
        assert book.get("weights_ready_s", 0.0) >= call_s
    finally:
        gate.set()
        gen.engine.shutdown()


def test_a_train_workers_setup_is_its_construct(monkeypatch):
    from ray_tpu.train.jax.config import _setup_worker

    for var in ("RAY_TPU_TRAIN_RANK", "RAY_TPU_TRAIN_WORLD_SIZE",
                "RAY_TPU_TRAIN_COORDINATOR"):
        monkeypatch.setenv(var, "")  # put back when the test ends
    before = book_now()
    _setup_worker(0, 1, "localhost:1", {"use_jax_distributed": False})
    assert os.environ["RAY_TPU_TRAIN_RANK"] == "0"
    assert moved(before, "startup.construct") > 0


# ---------------------------------------------------------------------------
# the compile book
# ---------------------------------------------------------------------------
def test_watch_compiles_twice_registers_one_listener():
    from jax._src import monitoring

    assert compile_cache.watch_compiles()
    assert compile_cache.watch_compiles()
    watch = compile_cache._WATCH
    for ours, registered in (
            (watch.on_seconds, monitoring.get_event_duration_listeners()),
            (watch.on_start, monitoring.get_scalar_listeners()),
            (watch.on_event, monitoring.get_event_listeners())):
        assert registered.count(ours) == 1


@pytest.fixture
def own_watch():
    """A book of the test's own beside the process's (whose `slowest` holds
    whatever the process compiled before): listeners registered for the
    test and taken away after it."""
    import jax

    watch = compile_cache._Watch()
    listeners = ((watch.on_start, "scalar_listener"),
                 (watch.on_seconds, "event_duration_secs_listener"),
                 (watch.on_event, "event_listener"))
    for fn, kind in listeners:
        getattr(jax.monitoring, "register_" + kind)(fn)
    yield watch
    for fn, kind in listeners:
        getattr(jax.monitoring, "unregister_" + kind.replace(
            "_secs", ""))(fn)


def test_every_compilation_is_counted_once_and_a_reader_holds_a_whole_book(
        own_watch):
    import jax
    import numpy as np

    before = own_watch.snapshot
    assert before["programs"] == 0 and before["slowest"] == []

    @jax.jit
    def outer_57(x):
        # a jitted function traced inside another's trace: its seconds are
        # inside the outer one's, and one program comes of the two
        return jax.jit(lambda y: y * 3)(x) + 1

    # read on the host: indexing a device array is a program of its own
    assert np.asarray(outer_57(np.ones(4, np.float32)))[0] == 4
    after = own_watch.snapshot
    assert after is not before and before["programs"] == 0, \
        "an event replaces the book and leaves the one a reader holds"
    assert after["programs"] == 1
    assert after["backend_s"] > 0 and after["lower_s"] > 0
    (name, python_s, backend_s), = after["slowest"]
    assert "outer_57" in name and backend_s == after["backend_s"]
    # the outermost trace alone: Python's side is the one program's, not
    # that and the inner trace again
    assert after["trace_s"] > 0
    assert after["trace_s"] + after["lower_s"] == pytest.approx(
        python_s, rel=1e-6)
    outer_57(np.ones(4, np.float32))  # a cached dispatch raises no event
    assert own_watch.snapshot is after
    # the process's own book moved with it, by the same program
    assert compile_cache.compile_stats()["programs"] >= 1


def test_the_book_keeps_the_longest_programs_and_no_more():
    watch = compile_cache._Watch()
    for i in range(compile_cache.SLOWEST_KEPT + 4):
        watch.on_seconds(compile_cache.LOWER_EVENT, 0.5, fun_name=f"p{i}")
        watch.on_seconds(compile_cache.BACKEND_EVENT, float(i),
                         fun_name=f"p{i}")
    book = watch.snapshot
    assert book["programs"] == compile_cache.SLOWEST_KEPT + 4
    assert len(book["slowest"]) == compile_cache.SLOWEST_KEPT
    assert book["slowest"][0] == ("p19", 0.5, 19.0)
    assert [p[0] for p in book["slowest"]][-1] == "p4"


CACHE_SCRIPT = """
import json, sys
import jax, numpy as np
from ray_tpu._private import compile_cache
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
assert compile_cache.watch_compiles()
f = jax.jit(lambda x: x * 2 + 1)
f(np.ones(4, np.float32)).block_until_ready()
first = compile_cache.compile_stats()
jax.clear_caches()
f(np.ones(4, np.float32)).block_until_ready()
print(json.dumps([first, compile_cache.compile_stats()]))
"""


def test_the_persistent_cache_counts_one_miss_then_one_hit(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", CACHE_SCRIPT, str(tmp_path / "cache")],
        env=env, capture_output=True, text=True, timeout=150,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    first, second = json.loads(proc.stdout.splitlines()[-1])
    assert (first["programs"], first["cache_requests"],
            first["cache_misses"], first["cache_hits"]) == (1, 1, 1, 0)
    assert (second["programs"], second["cache_requests"],
            second["cache_misses"], second["cache_hits"]) == (2, 2, 1, 1)
    assert first["cache_saved_s"] == 0.0 and second["cache_saved_s"] != 0.0
    assert second["cache_retrieval_s"] > 0.0
    assert second["backend_s"] > first["backend_s"] > 0.0


# ---------------------------------------------------------------------------
# the step that compiles, and the ring
# ---------------------------------------------------------------------------
def test_a_request_at_an_unwarmed_length_is_a_step_that_compiled(
        armed_recorder):
    gen = small_generator()
    try:
        gen.warm_step_programs(8)
        stats = gen.engine_stats()
        assert stats["step_compiles"] == 0 and stats["step_compile_s"] == 0
        assert events.startup_stats()["warm_s"][8] > 0
        armed_recorder.drain()
        # 3 to 5 tokens: the warmed 2 x 8
        assert len(list(gen({"prompt": [3, 5, 7], "max_new": 2}))) == 2
        assert gen.engine_stats()["step_compiles"] == 0
        assert not [s for s in drained(armed_recorder)
                    if s["name"] == "compile"]
        # 9 and 10 tokens: 2 x 16, which nobody warmed
        assert len(list(gen({"prompt": list(range(2, 11)),
                             "max_new": 2}))) == 2
        stats = gen.engine_stats()
        assert stats["step_compiles"] == 1 and stats["step_compile_s"] > 0
        assert stats["compiles"] is compile_cache.compile_stats()
        assert stats["startup"] is gen.engine_stats()["startup"]
        assert stats["startup"] is events.startup_stats()
        assert gen.device_info()["compiles"] is stats["compiles"]
    finally:
        gen.engine.shutdown()
    spans = drained(armed_recorder)
    compiled, = [s for s in spans if s["name"] == "compile"]
    device, = [s for s in spans if s["span"] == compiled["parent"]]
    assert device["name"] == "llm.device"
    assert compiled["trace"] == device["trace"]
    assert "step_fn" in compiled["extra"]["fun_name"]
    assert compiled["dur_us"] == pytest.approx(
        1e6 * stats["step_compile_s"], abs=2)


def test_recorder_off_no_startup_slot_is_written_and_armed_they_are_one_trace(
        armed_recorder):
    from ray_tpu.serve.llm import LlamaGenerator

    armed_recorder.enabled = False
    written = armed_recorder.counter
    with events.startup_span("construct"):
        with events.startup_span("devices"):
            pass
    events.startup_record("startup.boot", time.time(), 0.1)
    assert events.startup_root() is None
    assert armed_recorder.counter == written

    armed_recorder.enabled = True
    replica = replica_of(
        LlamaGenerator, config="debug_1l", lora_rank=2, max_batch_size=2,
        allowed_batch_sizes=(2,), max_new_tokens=4, seq_bucket=8)
    try:
        replica._callable.warm_step_programs(8)
        events.startup_record("startup.boot", time.time() - 1.0, 0.25,
                              {"actor_start": "fork"})
        deadline = time.time() + 30
        spans = drained(armed_recorder)
        while (not any(s["name"] == "startup.weights_ready" for s in spans)
               and time.time() < deadline):
            time.sleep(0.01)
            spans += drained(armed_recorder)
    finally:
        replica._callable.engine.shutdown()
    starts = {s["name"]: s for s in spans if s["cat"] == "startup"}
    assert set(starts) == {
        "startup.boot", "startup.construct", "startup.devices",
        "startup.weights", "startup.weights_ready", "startup.warm"}
    assert len({s["trace"] for s in starts.values()}) == 1
    assert (starts["startup.boot"]["trace"], 0) == events.startup_root()
    construct = starts["startup.construct"]
    for name in ("startup.boot", "startup.construct", "startup.warm",
                 "startup.weights_ready"):
        assert starts[name]["parent"] == 0, name
    for name in ("startup.devices", "startup.weights"):
        assert starts[name]["parent"] == construct["span"], name
    assert starts["startup.boot"]["extra"] == {"actor_start": "fork"}
    assert starts["startup.warm"]["extra"] == {"seq_len": 8}
    assert starts["startup.weights_ready"]["dur_us"] \
        >= starts["startup.weights"]["dur_us"]
    # the compilations of the start-up hang under the phase they fell in
    by_parent = {}
    for s in spans:
        if s["name"] == "compile":
            by_parent.setdefault(s["parent"], []).append(s)
    assert by_parent[starts["startup.warm"]["span"]]
    assert by_parent[starts["startup.weights"]["span"]]
    # one lane of the timeline: every start-up slice shares a tid
    slices = [e for e in events.to_chrome_trace(list(starts.values()))
              if e["ph"] == "X"]
    assert len(slices) == 6 and len({e["tid"] for e in slices}) == 1


# ---------------------------------------------------------------------------
# a worker of the real runtime on a fake chip: its boot and its lease
# ---------------------------------------------------------------------------
def _worker_book():
    return {"book": events.startup_stats(),
            "hooks": [f.module for f in sys.meta_path
                      if isinstance(f, events._FirstImport)],
            "jax": "jax" in sys.modules, "pid": os.getpid()}


@pytest.fixture
def traced_fake_chip():
    import ray_tpu

    os.environ["RAY_TPU_NUM_CHIPS"] = "1"
    os.environ["RAY_TPU_TASK_EVENT_SAMPLE_RATE"] = "1"
    try:
        assert not ray_tpu.is_initialized()
        ray_tpu.init(num_cpus=2)
        yield
    finally:
        ray_tpu.shutdown()
        del os.environ["RAY_TPU_NUM_CHIPS"]
        del os.environ["RAY_TPU_TASK_EVENT_SAMPLE_RATE"]


def test_a_chip_actors_boot_and_lease_are_in_its_book_and_in_the_ring(
        traced_fake_chip):
    import ray_tpu

    @ray_tpu.remote
    class Holder:
        def book(self):
            return _worker_book()

    actor = Holder.options(num_tpus=1, num_cpus=1).remote()
    seen = ray_tpu.get(actor.book.remote(), timeout=60)
    book = seen["book"]
    assert book["startup.boot"] > 0 and book["startup.chip_bind"] > 0
    assert book["actor_start"] in ("warm_hit", "demand_hit", "fork")
    assert book["at"]["startup.boot"] <= book["at"]["startup.chip_bind"]
    # nothing imported jax for it: the hook waits for whoever does
    assert not seen["jax"] and seen["hooks"] == ["jax"]
    assert "startup.import_jax" not in book

    worker = ray_tpu._worker_mod.global_worker

    def start_up_spans():
        worker.flush_task_events(wait=True)
        spans = worker._acall(worker.head.call("ListSpans",
                                               {"limit": 50000}))
        return {s["name"]: s for s in spans
                if s["pid"] == seen["pid"] and s["cat"] == "startup"}

    deadline = time.time() + 30
    found = start_up_spans()
    while len(found) < 2 and time.time() < deadline:
        time.sleep(0.25)
        found = start_up_spans()
    ray_tpu.kill(actor)
    assert set(found) == {"startup.boot", "startup.chip_bind"}, found
    assert found["startup.boot"]["trace"] \
        == found["startup.chip_bind"]["trace"]
    assert found["startup.boot"]["extra"] == {
        "actor_start": book["actor_start"]}
    assert found["startup.boot"]["dur_us"] == pytest.approx(
        1e6 * book["startup.boot"], abs=2)
    assert found["startup.chip_bind"]["extra"] == {"chips": 1}
