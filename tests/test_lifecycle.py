"""Guaranteed-teardown gate (ISSUE 1): after any shutdown path — clean,
cluster, or chaotic — zero registered pids survive, zero session dirs
remain, and the driver's event loop dies without "Task was destroyed but
it is pending!" warnings. These are the leaks that turned the round-5
MULTICHIP gate red (22 orphan daemons + stale /dev/shm segments starving
the next run).
"""

import logging
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait_all_dead(session_dir: str, timeout_s: float = 10.0):
    """Poll the registry until every registered pid is dead; returns the
    stragglers (empty list = success)."""
    from ray_tpu._private import lifecycle

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if not os.path.exists(session_dir):
            return []
        live = lifecycle.live_registered(session_dir)
        if not live:
            return []
        time.sleep(0.25)
    return lifecycle.live_registered(session_dir) \
        if os.path.exists(session_dir) else []


class _AsyncioWarnings(logging.Handler):
    def __init__(self):
        super().__init__()
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


@pytest.fixture
def asyncio_log():
    handler = _AsyncioWarnings()
    logger = logging.getLogger("asyncio")
    logger.addHandler(handler)
    yield handler
    logger.removeHandler(handler)


def test_shutdown_reaps_everything(asyncio_log):
    import ray_tpu
    from ray_tpu._private import lifecycle

    ray_tpu.init(num_cpus=2)
    node = ray_tpu._global_node
    session_dir = node.session_dir

    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get([f.remote(i) for i in range(4)]) == [1, 2, 3, 4]
    # daemons + at least one worker must be in the registry before stop
    roles = {r["role"] for r in lifecycle.live_registered(session_dir)}
    assert {"gcs", "agent"} <= roles, roles
    assert "worker" in roles, roles
    recorded = lifecycle.live_registered(session_dir)

    ray_tpu.shutdown()

    for rec in recorded:
        assert not lifecycle._pid_alive(rec["pid"], rec.get("create_time")), \
            f"{rec['role']} pid {rec['pid']} survived shutdown"
    assert not os.path.exists(session_dir), \
        "session dir (shm segments) survived shutdown"
    pending = [m for m in asyncio_log.messages if "pending" in m]
    assert not pending, pending


def _in_process_table(rec) -> bool:
    """The pid still has an entry that is the recorded process's, defunct
    or not (`lifecycle._pid_alive` calls a zombie dead)."""
    from ray_tpu._private import lifecycle

    try:
        os.kill(rec["pid"], 0)
    except ProcessLookupError:
        return False
    now = lifecycle._proc_create_time(rec["pid"])
    return now is None or rec.get("create_time") is None or \
        abs(now - rec["create_time"]) < 1e-6


@pytest.mark.parametrize("worker", ["idle", "deaf", "chip_holder"])
def test_shutdown_leaves_no_entry_in_the_process_table(worker, monkeypatch):
    """After `shutdown()` none of the session's pids is in the process
    table, zombies included: a defunct worker handed to pid 1 may still
    hold its chip. ``deaf`` is a worker that never runs its SIGTERM
    handler (stopped here; wedged in native code in the field) and is
    SIGKILLed after the grace; ``chip_holder`` leases a (fake) chip and is
    SIGKILLed at once, as `WorkerHandle.terminate` does."""
    import ray_tpu
    from ray_tpu._private import lifecycle

    if worker == "chip_holder":
        monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "1")
    ray_tpu.init(num_cpus=2)
    try:
        session_dir = ray_tpu._global_node.session_dir

        @ray_tpu.remote
        class A:
            def pid(self):
                return os.getpid()

        options = {"num_tpus": 1} if worker == "chip_holder" else {}
        pid = ray_tpu.get(A.options(**options).remote().pid.remote(),
                          timeout=60)
        recorded = lifecycle.list_registered(session_dir)
        assert {"gcs", "agent", "forkserver", "worker"} <= \
            {r["role"] for r in recorded}, recorded
        assert pid in {r["pid"] for r in recorded}
        if worker != "idle":
            os.kill(pid, signal.SIGSTOP)
    finally:
        ray_tpu.shutdown()

    left = [r for r in recorded if _in_process_table(r)]
    assert not left, f"still in the process table after shutdown: {left}"
    assert not os.path.exists(session_dir)


def test_wait_gone_sees_a_zombie_and_ends_at_its_limit():
    """`wait_gone` alone, on a killed grandchild: defunct under a parent
    that never reaps it, it is dead to `_pid_alive` and still there, and
    the wait ends at its limit with it; once its parent is gone too it is
    pid 1's to reap, and the wait ends when that has happened."""
    from ray_tpu._private import lifecycle

    parent = subprocess.Popen(
        [sys.executable, "-c",
         "import subprocess, sys, time\n"
         "g = subprocess.Popen([sys.executable, '-c',"
         " 'import time; time.sleep(120)'], start_new_session=True)\n"
         "print(g.pid, flush=True)\n"
         "time.sleep(120)\n"], stdout=subprocess.PIPE, text=True)
    try:
        grandchild = int(parent.stdout.readline())
        rec = {"pid": grandchild,
               "create_time": lifecycle._proc_create_time(grandchild)}
        os.kill(grandchild, signal.SIGKILL)
        deadline = time.monotonic() + 10
        while lifecycle._pid_alive(grandchild, rec["create_time"]):
            assert time.monotonic() < deadline, "SIGKILL never landed"
            time.sleep(0.02)
        t0 = time.monotonic()
        assert lifecycle.wait_gone([rec], timeout_s=0.3) == [rec]
        assert 0.3 <= time.monotonic() - t0 < 3.0
    finally:
        parent.kill()
        parent.wait(timeout=10)
    assert lifecycle.wait_gone([rec], timeout_s=30.0) == []
    assert not _in_process_table(rec)


_TERMINATE_TREE_CALLER = """\
import os, subprocess, sys, time
from ray_tpu._private import lifecycle

leader = sys.argv[1] == "leader"
# the child starts a grandchild in its own group and waits for it
child = subprocess.Popen(
    [sys.executable, "-c",
     "import subprocess, sys, time\\n"
     "g = subprocess.Popen([sys.executable, '-c',"
     " 'import time; time.sleep(120)'])\\n"
     "print(g.pid, flush=True)\\n"
     "time.sleep(120)\\n"],
    stdout=subprocess.PIPE, text=True, start_new_session=leader)
grandchild = int(child.stdout.readline())


class NotMyChild:  # the agent's stand-in for a worker it did not start
    pid = child.pid

    def poll(self):
        return child.poll()


lifecycle.terminate_tree([NotMyChild()], sigterm_timeout_s=5.0)
time.sleep(0.3)
try:  # running, or defunct until pid 1 has reaped it
    with open(f"/proc/{grandchild}/stat") as f:
        state = f.read().rsplit(")", 1)[1].split()[0]
except OSError:
    state = "Z"
print("grandchild gone" if state == "Z" else "grandchild alive", flush=True)
if state != "Z":
    os.kill(grandchild, 9)
print("caller survived", flush=True)
"""


@pytest.mark.parametrize("child", ["member", "leader"])
def test_terminate_tree_signals_a_group_only_through_its_leader(child):
    """A process that leads its group is its tree and dies with it; a
    process that is a member of its STARTER's group (a C++ worker run
    from a shell or from a test, registered with the agent) is signalled
    alone. Signalled by group, it took its starter along: an agent's
    teardown ended the whole pytest run that had started the worker
    (rc 143, two whole runs of five; PR 51)."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [REPO] + ([os.environ["PYTHONPATH"]]
                  if os.environ.get("PYTHONPATH") else [])))
    # a session of its own: whatever the caller signals is not this run
    proc = subprocess.run(
        [sys.executable, "-c", _TERMINATE_TREE_CALLER, child],
        capture_output=True, text=True, timeout=120, env=env,
        start_new_session=True)
    assert proc.returncode == 0, (proc.returncode, proc.stdout, proc.stderr)
    assert "caller survived" in proc.stdout
    # a leader's group went with it; a member's group is not the
    # runtime's to signal, so what the member started lives on
    want = "grandchild gone" if child == "leader" else "grandchild alive"
    assert want in proc.stdout, proc.stdout


def test_cluster_teardown_reaps_everything():
    import ray_tpu
    from ray_tpu._private import lifecycle
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    cluster.add_node(num_cpus=2)
    ray_tpu.init(_node=cluster.head_node)
    cluster.wait_for_nodes()
    session_dir = cluster.session_dir

    @ray_tpu.remote
    def g():
        return os.getpid()

    ray_tpu.get([g.remote() for _ in range(4)])
    recorded = lifecycle.live_registered(session_dir)
    assert len(recorded) >= 3  # gcs + 2 agents at minimum

    ray_tpu.shutdown()
    cluster.shutdown()

    for rec in recorded:
        assert not lifecycle._pid_alive(rec["pid"], rec.get("create_time")), \
            f"{rec['role']} pid {rec['pid']} survived cluster teardown"
    assert not os.path.exists(session_dir)


def test_driver_sigkill_fate_sharing():
    """SIGKILL the driver mid-workload: PDEATHSIG + the supervisor-poll
    watchdog must reap gcs/agent/forkserver/workers within 10s."""
    from ray_tpu._private import lifecycle

    driver_src = (
        "import ray_tpu, time\n"
        "ray_tpu.init(num_cpus=2)\n"
        "@ray_tpu.remote\n"
        "class A:\n"
        "    def ping(self): return 'ok'\n"
        "a = A.remote()\n"
        "assert ray_tpu.get(a.ping.remote()) == 'ok'\n"
        "print('READY', ray_tpu._global_node.session_dir, flush=True)\n"
        "time.sleep(600)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen([sys.executable, "-c", driver_src],
                            stdout=subprocess.PIPE, text=True, env=env)
    session_dir = None
    try:
        deadline = time.monotonic() + 120
        for line in proc.stdout:
            if line.startswith("READY"):
                session_dir = line.split()[1]
                break
            if time.monotonic() > deadline:
                break
        assert session_dir, "driver never became ready"
        assert lifecycle.live_registered(session_dir), \
            "no registered daemons before the kill"
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)
        stragglers = _wait_all_dead(session_dir, timeout_s=10.0)
        assert not stragglers, \
            f"daemons survived driver SIGKILL: {stragglers}"
    finally:
        if proc.poll() is None:
            proc.kill()
        if session_dir and os.path.exists(session_dir):
            lifecycle.reap_session(session_dir, remove=True)


def test_agent_sigkill_chaos_reaps_workers():
    """util.chaos.DaemonKiller SIGKILLs the node agent mid-workload: the
    agent's subtree (forkserver + workers) fate-shares with it and must
    die; shutdown() then reaps the rest of the session."""
    import ray_tpu
    from ray_tpu._private import lifecycle
    from ray_tpu.util.chaos import DaemonKiller

    ray_tpu.init(num_cpus=2)
    session_dir = ray_tpu._global_node.session_dir
    try:
        @ray_tpu.remote
        def h(x):
            return x * 2

        assert ray_tpu.get(h.remote(21)) == 42
        subtree = [r for r in lifecycle.live_registered(session_dir)
                   if r["role"] in ("agent", "forkserver", "worker")]
        assert subtree

        killer = DaemonKiller(session_dir, roles=("agent",),
                              interval_s=0.2, max_kills=1)
        killer.run()
        deadline = time.monotonic() + 10
        while not killer.kills and time.monotonic() < deadline:
            time.sleep(0.1)
        assert killer.stop(), "killer never found the agent"

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if all(not lifecycle._pid_alive(r["pid"], r.get("create_time"))
                   for r in subtree):
                break
            time.sleep(0.25)
        stragglers = [r for r in subtree
                      if lifecycle._pid_alive(r["pid"], r.get("create_time"))]
        assert not stragglers, \
            f"agent subtree survived agent SIGKILL: {stragglers}"
    finally:
        ray_tpu.shutdown()
    assert not os.path.exists(session_dir)


def test_compiled_dag_get_raises_on_dead_stage():
    """CompiledDAGRef.get(timeout=...) must raise within its timeout when
    a stage process is SIGKILL'd — not block forever."""
    import ray_tpu
    from ray_tpu.dag import InputNode

    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def stage(x):
            return (os.getpid(), x * 2)

        with InputNode() as inp:
            dag = stage.bind(inp)
        compiled = dag.experimental_compile()
        try:
            pid, v = compiled.execute(3).get(timeout=30)
            assert v == 6
            os.kill(pid, signal.SIGKILL)
            ref = compiled.execute(4)
            t0 = time.monotonic()
            with pytest.raises(Exception) as exc_info:
                ref.get(timeout=15)
            elapsed = time.monotonic() - t0
            assert elapsed < 15, "get() burned the whole timeout"
            assert not isinstance(exc_info.value, TimeoutError), \
                "dead stage surfaced as a bare timeout, not an error"
        finally:
            compiled.teardown(timeout=5)
    finally:
        ray_tpu.shutdown()


def test_stale_session_gc():
    """gc_stale_sessions removes session dirs whose registered pids are
    all dead, and leaves live sessions alone."""
    import tempfile

    from ray_tpu._private import lifecycle

    root = tempfile.mkdtemp(prefix="ray_tpu_gc_test_")
    try:
        # dead session: register a process that exits immediately
        dead = os.path.join(root, "session_dead")
        os.makedirs(dead)
        proc = subprocess.Popen([sys.executable, "-c", "pass"])
        proc.wait()
        lifecycle.register_process(dead, "agent", proc.pid)
        # live session: register ourselves via a child that stays alive
        live = os.path.join(root, "session_live")
        os.makedirs(live)
        sleeper = subprocess.Popen([sys.executable, "-c",
                                    "import time; time.sleep(60)"])
        lifecycle.register_process(live, "agent", sleeper.pid)
        try:
            removed = lifecycle.gc_stale_sessions([root])
            assert dead in removed
            assert not os.path.exists(dead)
            assert os.path.exists(live), "GC removed a LIVE session"
            # kill_live (stop --all) takes the live one too
            removed = lifecycle.gc_stale_sessions([root], kill_live=True)
            assert live in removed
            assert not os.path.exists(live)
            assert sleeper.poll() is not None or \
                _wait_pid_dead(sleeper, 5.0)
        finally:
            if sleeper.poll() is None:
                sleeper.kill()
    finally:
        import shutil

        shutil.rmtree(root, ignore_errors=True)


def _wait_pid_dead(proc, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            return True
        time.sleep(0.1)
    return False


# ---------------------------------------------------------------------------
# The suite's own gates (ISSUE 24): tests/conftest.py sweeps leaked
# sessions once a run, in the process that owns the run, and gives every
# tier-1 test a time limit. Each test below drives an inner pytest run in
# a directory of its own that takes the repo's conftest in as a plugin.
#
# An inner run whose sweep looked at the shared session roots would kill
# the clusters of the OUTER run's other workers — the very fault these
# tests guard. So the inner conftest first points the driver side's two
# root lookups at a root of its own (daemons are handed their
# session_dir, so nothing else needs it), and only then loads the hooks.
# Inner test files are tier-1 tests to the hooks (limit, ref-leak gate), as
# every file is that SLOW_FILES does not name.
# ---------------------------------------------------------------------------
_INNER_CONFTEST = """\
import importlib.util
import sys

from ray_tpu._private import lifecycle, node

lifecycle.default_session_roots = lambda: [{root!r}]
node.default_session_root = lambda: {root!r}

spec = importlib.util.spec_from_file_location(
    "repo_conftest", {repo!r} + "/tests/conftest.py")
repo_conftest = importlib.util.module_from_spec(spec)
sys.modules["repo_conftest"] = repo_conftest
spec.loader.exec_module(repo_conftest)
{extra}
pytest_plugins = ("repo_conftest",)
"""

_RUN_MODES = {
    "serial": ["-p", "no:xdist"],
    "xdist": ["-p", "xdist", "-n", "2", "--dist", "loadfile"],
}


@pytest.fixture
def inner_root():
    """A session root for one inner run: short (unix socket paths live
    under it), and swept here of whatever the inner run left, its
    deliberate leak included should the gate under test miss it."""
    import shutil
    import tempfile

    from ray_tpu._private import lifecycle

    root = tempfile.mkdtemp(
        prefix="rt_", dir="/dev/shm" if os.path.isdir("/dev/shm") else None)
    yield root
    for sess in lifecycle.list_sessions([root]):
        lifecycle.reap_session(sess["path"], remove=True)
    shutil.rmtree(root, ignore_errors=True)


def _inner_pytest(tmp_path, root, mode, files, extra=""):
    # an ini of its own: the rootdir is here, and the repo's does not apply
    (tmp_path / "pytest.ini").write_text("[pytest]\n")
    (tmp_path / "conftest.py").write_text(
        _INNER_CONFTEST.format(repo=REPO, root=root, extra=extra))
    for name, body in files.items():
        (tmp_path / name).write_text(body)
    # the inner run owns itself: it is nobody's xdist worker
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_XDIST")}
    # it runs outside the checkout, and so do the daemons it starts
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         *_RUN_MODES[mode], str(tmp_path)],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=150)
    return proc.returncode, proc.stdout + proc.stderr


_ENDS_FIRST = """\
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def test_ends_first():
    with open(os.path.join(HERE, "a_pid.tmp"), "w") as f:
        f.write(str(os.getpid()))
    os.replace(os.path.join(HERE, "a_pid.tmp"), os.path.join(HERE, "a_pid"))
    deadline = time.monotonic() + 60
    while not os.path.exists(os.path.join(HERE, "b_up")):
        assert time.monotonic() < deadline, "the neighbour never came up"
        time.sleep(0.1)
"""

_HOLDS_CLUSTER = """\
import os
import time

import ray_tpu

HERE = os.path.dirname(os.path.abspath(__file__))


def test_cluster_outlives_neighbour():
    # start after the neighbour's worker is under way, so that the
    # cluster is in no process's start-of-run baseline
    deadline = time.monotonic() + 60
    while not os.path.exists(os.path.join(HERE, "a_pid")):
        assert time.monotonic() < deadline, "the neighbour never started"
        time.sleep(0.1)
    ray_tpu.init(num_cpus=1)
    try:
        open(os.path.join(HERE, "b_up"), "w").close()
        with open(os.path.join(HERE, "a_pid")) as f:
            neighbour = f.read()
        while not os.path.exists(os.path.join(HERE, "ended_" + neighbour)):
            assert time.monotonic() < deadline, "the neighbour never ended"
            time.sleep(0.1)
        time.sleep(2.0)  # longer than a reaper's SIGTERM takes to land

        @ray_tpu.remote
        def f():
            return 7

        assert ray_tpu.get(f.remote(), timeout=30) == 7
    finally:
        ray_tpu.shutdown()
"""


# a worker's process outlives its session (xdist keeps the gateway), so
# the inner conftest says when each process's session, fixtures and all,
# is over
_MARK_PROCESS_ENDED = """
def pytest_sessionfinish(session):
    import os

    open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      f"ended_{os.getpid()}"), "w").close()
"""


def test_gate_spares_a_neighbour_workers_cluster(tmp_path, inner_root):
    """Under xdist one worker runs out of files while another still
    holds a cluster: the cluster answers a task afterwards, and the run
    passes. (The per-worker gate reaped it: ISSUE 24.)"""
    rc, out = _inner_pytest(tmp_path, inner_root, "xdist", {
        "test_core_api.py": _ENDS_FIRST,
        "test_actors.py": _HOLDS_CLUSTER,
    }, extra=_MARK_PROCESS_ENDED)
    assert rc == 0, out
    assert "2 passed" in out, out
    assert "sessions leaked" not in out, out


_LEAKS_SESSION = """\
import os
import subprocess
import sys

from ray_tpu._private import lifecycle

HERE = os.path.dirname(os.path.abspath(__file__))


def test_leaves_a_session_behind():
    sleeper = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(120)"])
    session = os.path.join(lifecycle.default_session_roots()[0],
                           "session_left_behind")
    os.makedirs(session)
    lifecycle.register_process(session, "agent", sleeper.pid)
    with open(os.path.join(HERE, "sleeper_pid"), "w") as f:
        f.write(str(sleeper.pid))
"""

_PASSES = """\
def test_passes():
    pass
"""


@pytest.mark.parametrize("mode", sorted(_RUN_MODES))
def test_gate_fails_the_run_on_a_leaked_session(tmp_path, inner_root, mode):
    """A session that outlives the run fails the RUN — every test of it
    passed, none is blamed with an error at its teardown — is named once
    with its live pid, and is reaped."""
    from ray_tpu._private import lifecycle

    rc, out = _inner_pytest(tmp_path, inner_root, mode, {
        "test_core_api.py": _LEAKS_SESSION,
        "test_actors.py": _PASSES,
    })
    session = os.path.join(inner_root, "session_left_behind")
    sleeper = int((tmp_path / "sleeper_pid").read_text())
    assert rc == 1, out
    assert "2 passed" in out and "error" not in out.lower(), out
    assert out.count(session) == 1, out
    assert f"{session} [live: agent:{sleeper}]" in out, out
    assert not os.path.exists(session), "leaked session was not reaped"
    assert not lifecycle._pid_alive(sleeper), "leaked pid was not reaped"


_BLOCKS = """\
import queue


def test_blocks_for_ever():
    queue.Queue().get()


def test_next_one_still_runs():
    pass
"""


@pytest.mark.parametrize("mode", sorted(_RUN_MODES))
def test_time_limit_fails_a_wedged_test(tmp_path, inner_root, mode):
    """A test that sits in a wait without a timeout is failed by the
    harness's limit (shortened here by patching the constant), its stack
    is in the report, and the file's next test runs and passes."""
    rc, out = _inner_pytest(tmp_path, inner_root, mode, {
        "test_lifecycle.py": _BLOCKS,
    }, extra="repo_conftest.TEST_TIME_LIMIT_S = 2.0")
    assert rc == 1, out
    assert "1 failed, 1 passed" in out, out
    assert "exceeded the per-test time limit of 2 s" in out, out
    # where it sat: the test's own line and the wait inside queue.py
    assert "queue.Queue().get()" in out and "queue.py" in out, out
