"""Actor scale-out (ISSUE 10): warm worker pools, batched
lease/registration RPCs, and O(1) scheduler state.

Unit layers (no cluster): warm-pool lease handout liveness (conn-closed
and death-ledger pids are never leased), the forkserver death-ledger
consumer, idle-TTL reap accounting, the batch-size histogram, the
head's incremental scheduler indexes (state counts, node/job buckets,
committed-resources ledger, utilization rank), and the
CreateActorBatch/ActorReadyBatch framing round trip against a
HeadServer with fake connections.

Integration: a 200-actor burst rides the warm pool (hit counter
asserted) with batched readiness reports; DaemonKiller-style SIGKILL of
a parked warm worker and then of a just-leased worker degrades to cold
forks — creation still completes, no hang (the pid-registry-converges
check is the conftest session leak gate). A parked warm worker must
never have imported jax (MULTICHIP dryrun gate contract).
"""

import asyncio
import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu._private.agent import WorkerHandle, _ForeignProc, _note_hist
from ray_tpu._private.gcs import (
    ACTOR_ALIVE, ACTOR_DEAD, ACTOR_PENDING, HeadServer, _NodeRank)
from ray_tpu._private.resources import ResourceSet


# ---------------------------------------------------------------------------
# unit: warm pool handout + death ledger
# ---------------------------------------------------------------------------
class _FakeConn:
    closed = False

    def __init__(self):
        self.meta = {}
        self.pushes = []

    async def push(self, method, payload):
        self.pushes.append((method, payload))

    def push_nowait(self, method, payload):
        self.pushes.append((method, payload))


def _mini_agent(tmp_path):
    """A NodeAgent with real state tables but no started loops/servers."""
    from ray_tpu._private.agent import NodeAgent

    store = tmp_path / "store"
    sess = tmp_path / "session"
    os.makedirs(store, exist_ok=True)
    os.makedirs(sess, exist_ok=True)
    return NodeAgent(
        node_id="deadbeef" * 4, session_dir=str(sess), store_dir=str(store),
        head_host="127.0.0.1", head_port=1, resources={"CPU": 4.0},
        object_store_memory=1 << 20)


def _registered_handle(pid=0):
    h = WorkerHandle(os.urandom(16).hex(), proc=_ForeignProc(pid))
    h.registered.set()
    h.conn = _FakeConn()
    return h


class TestWarmPoolUnits:
    def test_lease_prefers_live_pristine_worker(self, tmp_path):
        agent = _mini_agent(tmp_path)
        live = _registered_handle(pid=os.getpid())
        agent.idle_workers.append(live)
        agent.workers[live.worker_id] = live
        got = agent._lease_warm_worker()
        assert got is live
        assert agent.idle_workers == []

    def test_closed_conn_is_never_leased(self, tmp_path):
        agent = _mini_agent(tmp_path)
        stale = _registered_handle(pid=os.getpid())
        stale.conn.closed = True
        agent.idle_workers.append(stale)
        assert agent._lease_warm_worker() is None

    def test_death_ledger_pid_is_never_leased(self, tmp_path):
        """A warm worker reaped by the forkserver's SIGCHLD handler has
        no connection to drop and its pid may be recycled — the ledger
        is the only truthful death signal for that window."""
        agent = _mini_agent(tmp_path)
        # pid of THIS process: kill(pid, 0) says alive, i.e. exactly the
        # recycled-pid shape the ledger exists to catch
        victim = _registered_handle(pid=os.getpid())
        agent.idle_workers.append(victim)
        agent.workers[victim.worker_id] = victim
        agent._pid_handles[os.getpid()] = victim
        with open(agent._forkserver_sock + ".deaths", "w") as f:
            f.write(f"{os.getpid()}\n")

        async def run():
            assert agent._lease_warm_worker() is None
            # the exit handler was scheduled; let it run
            await asyncio.sleep(0)

        asyncio.run(run())
        assert victim.force_dead
        assert not victim.alive

    def test_ledger_consumed_incrementally(self, tmp_path):
        agent = _mini_agent(tmp_path)
        path = agent._forkserver_sock + ".deaths"
        with open(path, "w") as f:
            f.write("999999999\n")

        async def run():
            agent._consume_death_ledger()
            pos = agent._death_ledger_pos
            agent._consume_death_ledger()  # nothing new: offset stable
            assert agent._death_ledger_pos == pos

        asyncio.run(run())

    def test_warm_target_auto_and_disable(self, tmp_path, monkeypatch):
        agent = _mini_agent(tmp_path)
        monkeypatch.setenv("RAY_TPU_WORKER_POOL_WARM_TARGET", "0")
        assert agent.WARM_TARGET == 4  # max(2, num_cpus)
        assert agent.warm_lease_enabled
        monkeypatch.setenv("RAY_TPU_WORKER_POOL_WARM_TARGET", "-1")
        assert agent.WARM_TARGET == 0
        assert not agent.warm_lease_enabled
        live = _registered_handle(pid=os.getpid())
        agent.idle_workers.append(live)
        assert agent._lease_warm_worker() is None  # disabled: cold path

    def test_batch_hist_buckets(self):
        hist = {}
        for n in (1, 2, 3, 8, 64, 129, 500):
            _note_hist(hist, n)
        assert hist == {"1": 1, "2": 1, "4": 1, "8": 1, "64": 1, "128+": 2}


# ---------------------------------------------------------------------------
# unit: O(1) scheduler state
# ---------------------------------------------------------------------------
class TestSchedulerState:
    def test_node_rank_orders_and_updates(self):
        rank = _NodeRank()
        rank.update("a", 0.5)
        rank.update("b", 0.1)
        rank.update("c", 0.9)
        assert rank.ordered_ids() == ["b", "a", "c"]
        rank.update("c", 0.0)  # re-rank on resource report
        assert rank.ordered_ids() == ["c", "b", "a"]
        rank.remove("b")
        assert rank.ordered_ids() == ["c", "a"]
        assert "b" not in rank and "a" in rank
        rank.remove("b")  # idempotent
        assert len(rank) == 2

    def test_state_counts_and_committed_ledger(self, tmp_path):
        head = HeadServer(str(tmp_path), port=0)
        conn = _FakeConn()
        reply, info, op = head._admit_actor(conn, {
            "actor_id": "a1", "spec": {"resources": {"CPU": 1.0}},
            "name": "", "namespace": "default"})
        assert reply is None and op[0] == "actor_create"
        assert head._actor_state_counts == {ACTOR_PENDING: 1}
        req = ResourceSet({"CPU": 1.0})
        head._actor_set_node(info, "n1")
        head._commit_placement(info, req, "n1")
        assert head._committed_agg["n1"].get("CPU") == 1.0
        assert head._actors_by_node["n1"] == {"a1"}
        # readiness uncommits + re-counts
        head._apply_actor_ready(info, {"addr": {"host": "h", "port": 1},
                                       "pid": 7}, "n1")
        assert head._actor_state_counts == {ACTOR_ALIVE: 1}
        assert "n1" not in head._committed_agg
        # death drops the node bucket
        head._actor_set_state(info, ACTOR_DEAD)
        assert head._actor_state_counts == {ACTOR_DEAD: 1}
        assert "n1" not in head._actors_by_node

    def test_committed_ledger_ages_out(self, tmp_path, monkeypatch):
        head = HeadServer(str(tmp_path), port=0)
        conn = _FakeConn()
        _r, info, _op = head._admit_actor(conn, {
            "actor_id": "a1", "spec": {}, "name": "", "namespace": "d"})
        head._commit_placement(info, ResourceSet({"CPU": 1.0}), "n1")
        # entry older than the window is pruned on the next read
        head._committed_nodes["n1"]["a1"] = (
            time.monotonic() - head.COMMIT_WINDOW_S - 1,
            head._committed_nodes["n1"]["a1"][1])
        head._prune_committed("n1")
        assert "n1" not in head._committed_agg


# ---------------------------------------------------------------------------
# unit: batched framing round trip (HeadServer with fake conns)
# ---------------------------------------------------------------------------
class TestBatchedFraming:
    def test_create_and_ready_batch_round_trip(self, tmp_path):
        head = HeadServer(str(tmp_path), port=0)
        agent_conn = _FakeConn()

        async def run():
            from ray_tpu._private.gcs import NodeInfo
            from ray_tpu._private.resources import NodeResources

            node = NodeInfo("n1", {"host": "127.0.0.1", "port": 1},
                            NodeResources(ResourceSet({"CPU": 8.0})),
                            agent_conn)
            head.nodes["n1"] = node
            head._rank_update(node)
            driver = _FakeConn()
            items = [{"actor_id": f"a{i}",
                      "spec": {"resources": {"CPU": 0.01}},
                      "name": "", "namespace": "default"}
                     for i in range(5)]
            reply = await head._create_actor_batch(driver, {"items": items})
            assert [r["state"] for r in reply["results"]] == \
                [ACTOR_PENDING] * 5
            # one StartActorBatch frame, all five entries, to the node
            methods = [m for m, _ in agent_conn.pushes]
            assert methods.count("StartActorBatch") == 1
            batch = agent_conn.pushes[-1][1]["items"]
            assert {it["actor_id"] for it in batch} == \
                {f"a{i}" for i in range(5)}
            # duplicate delivery adopts instead of double-creating
            dup = await head._create_actor_batch(driver, {"items": items})
            assert all(r["state"] == ACTOR_PENDING
                       for r in dup["results"])
            assert len(head.actors) == 5
            # readiness batch flips every entry ALIVE in one call
            agent_conn.meta["node_id"] = "n1"
            ready = await head._actor_ready_batch(agent_conn, {
                "items": [{"actor_id": f"a{i}",
                           "addr": {"host": "h", "port": 2 + i},
                           "pid": 100 + i} for i in range(5)]})
            assert ready["n"] == 5
            assert head._actor_state_counts == {ACTOR_ALIVE: 5}
            assert all(head.actors[f"a{i}"].addr["port"] == 2 + i
                       for i in range(5))
            # per-entry blast radius: a taken name fails only its entry
            await head._create_actor(driver, {
                "actor_id": "named1", "spec": {}, "name": "dup",
                "namespace": "default"})
            mixed = await head._create_actor_batch(driver, {"items": [
                {"actor_id": "named2", "spec": {}, "name": "dup",
                 "namespace": "default"},
                {"actor_id": "b1", "spec": {}, "name": "",
                 "namespace": "default"},
            ]})
            assert "error" in mixed["results"][0]
            assert mixed["results"][1]["state"] == ACTOR_PENDING

        asyncio.run(run())


# ---------------------------------------------------------------------------
# integration: warm-pool burst + chaos
# ---------------------------------------------------------------------------
@pytest.fixture
def warm_cluster(monkeypatch):
    monkeypatch.setenv("RAY_TPU_WORKER_POOL_WARM_TARGET", "8")
    monkeypatch.setenv("RAY_TPU_WORKER_POOL_REFILL_INTERVAL_MS", "20")
    # a creation burst on this 2-core box can starve the agent loop of
    # CPU past the default 15s heartbeat budget (the node is BUSY, not
    # dead); these tests assert pool mechanics, not box timing
    monkeypatch.setenv("RAY_TPU_HEALTH_CHECK_FAILURE_THRESHOLD", "40")
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def _pool_stats():
    from ray_tpu._private import worker as wm

    w = wm.global_worker
    return w._acall(w.agent.call("GetWorkerPoolStats", {}, timeout=10),
                    timeout=15)


def _wait_pool(ready, what, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = _pool_stats()
        if ready(st):
            return st
        time.sleep(0.2)
    raise AssertionError(f"warm pool never {what}: {_pool_stats()}")


def _wait_warm(n, timeout=60):
    return _wait_pool(lambda st: st["warm"] >= n, f"reached {n}", timeout)


@ray_tpu.remote
class Probe:
    def __init__(self):
        import sys

        # recorded BEFORE any user import could pull jax in: a parked
        # warm worker pre-touching JAX/TPU state would break the
        # MULTICHIP dryrun gate's device ownership
        self.jax_preimported = "jax" in sys.modules

    def ping(self):
        return 1

    def jax_was_preimported(self):
        return self.jax_preimported

    def pid(self):
        return os.getpid()


class TestWarmPoolCluster:
    def test_burst_rides_pool_and_batches(self, warm_cluster):
        _wait_warm(4)
        before = _pool_stats()
        n = 100

        def burst(k):
            wave = [Probe.options(num_cpus=0.001).remote() for _ in range(k)]
            assert ray_tpu.get([a.ping.remote() for a in wave],
                               timeout=600) == [1] * k
            return wave

        # the front of the burst takes what is parked (4 at the least) and
        # drains the pool: more actors than the pool's target of 8, and an
        # actor keeps its worker
        actors = burst(n // 2)
        # the refill, as the pool reports it and not as the burst's timing
        # has it: forks counted as refills since `before`, and parked
        # again. A worker that registers while a start is waiting goes to
        # the waiter as a demand hit, so on a loaded box a burst without
        # this wait sees no parked refill at all.
        _wait_pool(lambda st: st["refills"] > before["refills"]
                   and st["warm"] >= 4, "refilled after the drain")
        actors += burst(n - n // 2)
        after = _pool_stats()
        hits = after["hits"] - before["hits"]
        # the pool serves the front of the burst + refills along the way
        assert hits >= 8, f"expected warm hits, got {after}"
        # readiness rode coalesced frames: at least one multi-entry batch
        multi = sum(v for k, v in after["ready_batch_hist"].items()
                    if k not in ("1",))
        assert multi >= 1, after["ready_batch_hist"]
        for a in actors:
            ray_tpu.kill(a)

    def test_warm_worker_never_imports_jax(self, warm_cluster):
        from ray_tpu._private.shm_rpc import SHM_STATS

        _wait_warm(2)
        before = _pool_stats()
        shm_before = SHM_STATS["calls_out"]
        probe = Probe.options(num_cpus=0.001).remote()
        assert ray_tpu.get(probe.jax_was_preimported.remote(),
                           timeout=120) is False
        after = _pool_stats()
        assert after["hits"] > before["hits"], \
            "probe was expected to ride a warm worker"
        # the new direct-call paths keep the gate contract too: the
        # probe's calls rode the shm lane (same node) and the parked
        # worker STILL never touched jax (mux/shm_rpc import none)
        assert SHM_STATS["calls_out"] > shm_before, \
            "same-node probe call did not ride the shm lane"

        # the batched fast path (ISSUE 18) keeps the gate contract too:
        # a map() batch through the warm pool leaves every executing
        # worker jax-free, and the driver's spec-template cache (the
        # fast path's signature memo) was actually exercised
        @ray_tpu.remote(num_cpus=0.001)
        def jax_loaded(i):
            import sys

            return "jax" in sys.modules

        assert ray_tpu.get(jax_loaded.map(range(8)),
                           timeout=120) == [False] * 8
        import ray_tpu._private.worker as _worker_mod

        assert _worker_mod.global_worker._spec_templates, \
            "map() batch did not populate the spec-template cache"
        ray_tpu.kill(probe)

    def test_kill_warm_then_leased_worker(self, warm_cluster):
        """SIGKILL a PARKED warm worker, then a JUST-LEASED one: creation
        falls back to cold forks, nothing hangs, and the pid registry
        converges (conftest leak gate asserts the final sweep)."""
        from ray_tpu._private import lifecycle, worker as wm

        st = _wait_warm(3)
        session_dir = wm.global_worker.session_dir
        assert session_dir
        # a parked warm worker = registered role=worker pid hosting no actor
        live = [r for r in lifecycle.live_registered(session_dir)
                if r.get("role") == "worker"]
        assert live, "no registered workers"
        os.kill(live[0]["pid"], signal.SIGKILL)
        time.sleep(0.5)
        # creation still completes (ledger/conn-drop evicts the corpse)
        a = Probe.options(num_cpus=0.001).remote()
        assert ray_tpu.get(a.ping.remote(), timeout=120) == 1
        # now SIGKILL a JUST-LEASED worker (the live actor's pid)
        pid = ray_tpu.get(a.pid.remote(), timeout=60)
        os.kill(pid, signal.SIGKILL)
        # a fresh creation must still work, promptly, with no hang
        b = Probe.options(num_cpus=0.001).remote()
        assert ray_tpu.get(b.ping.remote(), timeout=120) == 1
        ray_tpu.kill(b)
        assert st["warm_target"] == 8
