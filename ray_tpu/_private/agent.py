"""Per-node agent (raylet analog).

Parity with the reference raylet (reference: ``src/ray/raylet/node_manager.h``,
``worker_pool.h``, ``local_task_manager.h``): one agent per node owning the
worker pool (spawn/lease/kill), the local resource accounting + lease-based
scheduler with spillback (reference: ``cluster_task_manager.cc:44``,
``hybrid_scheduling_policy.h:50``), the shared-memory store accounting
(reference: plasma + ``local_object_manager.h``), placement-group bundle
reservations (reference: ``placement_group_resource_manager.h``), and the
node-to-node object transfer plane (reference: ``object_manager.h:117``
Push/Pull chunking).

One asyncio process. Local clients (driver, workers) connect over a unix
socket; remote agents and spilled-back submitters connect over TCP.
"""

from __future__ import annotations

import asyncio
import logging
import os
import subprocess
import sys
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import events as _events
from ray_tpu._private import lifecycle
from ray_tpu._private.async_util import (
    DecorrelatedJitterBackoff, spawn_tracked)
from ray_tpu._private.config import CONFIG
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.object_store import StoreDirectory
from ray_tpu._private.protocol import (
    AsyncRpcClient, Connection, ConnectionPool, RawData, RpcServer,
    retry_call, set_fault_self_id)
from ray_tpu._private.pull_manager import PullManager
from ray_tpu._private.resources import (
    TPU, NodeResources, ResourceSet, label_constraints_match)


def _note_hist(hist: Dict[str, int], n: int) -> None:
    """Power-of-two batch-size histogram bucket (`1`,`2`,`4`,...,`128+`)."""
    bucket = 1
    while bucket < n and bucket < 128:
        bucket *= 2
    label = f"{bucket}+" if bucket == 128 and n > 128 else str(bucket)
    hist[label] = hist.get(label, 0) + 1


def _env_key_language(env_key):
    """Top-level "language" of a canonical runtime_env key, or None — a
    nested env_vars value spelled 'language' must not be mistaken for a
    cross-language lease (env keys are json with sorted keys,
    task_spec.runtime_env_key)."""
    if not env_key:
        return None
    try:
        import json as _json

        env = _json.loads(env_key)
    except Exception:
        return None
    lang = env.get("language") if isinstance(env, dict) else None
    return lang if isinstance(lang, str) else None


class NodeFencedError(Exception):
    """The head rejected this agent's registration: the node's incarnation
    was fenced after a death verdict (we were partitioned away and the
    cluster moved on). The only safe move is to stop existing — any lease
    we still hold or object we would still serve is a zombie."""


class _NeverLaunched:
    """Sentinel proc for spawns that failed before producing a process."""

    pid = None

    def poll(self):
        return 1

    def terminate(self):
        pass


class WorkerHandle:
    def __init__(self, worker_id: str, proc: Optional[subprocess.Popen]):
        self.worker_id = worker_id
        # None while the spawn sits in the admission queue (the agent caps
        # concurrent process startups like the reference raylet's
        # maximum_startup_concurrency, worker_pool.h)
        self.proc = proc
        self.launched_at: Optional[float] = None
        self.conn: Optional[Connection] = None  # registration connection
        self.direct_addr: Optional[Dict] = None  # {"host","port","unix"} for PushTask
        self.registered = asyncio.Event()
        # set when the agent observes the worker gone (exit handler or
        # watchdog eviction): liveness watchers await this instead of
        # polling — 1,000 live actors at a 0.5s poll each cost the agent
        # loop ~2,000 timer wakeups + proc.poll syscalls per second
        self.exited = asyncio.Event()
        self.leased_to: Optional[str] = None  # lease id
        self.assigned_resources: Optional[ResourceSet] = None
        self.is_actor = False
        self.actor_id: Optional[str] = None
        self.spawn_time = time.monotonic()
        self.idle_since = time.monotonic()
        # runtime_env this process has applied (None = pristine). A worker
        # that applied one env can never serve a different one (reference:
        # worker_pool keys processes by runtime-env hash, worker_pool.h).
        self.env_key: Optional[str] = None
        # accelerator instance ids this process was handed ({"TPU": [0]}),
        # from the node or from the bundle it was placed in
        self.chips: Dict[str, list] = {}
        self.used = False  # has been handed a lease or an actor

    # set when the forkserver's death ledger reported this pid reaped —
    # authoritative even if the OS has recycled the pid (poll can't tell)
    force_dead = False

    @property
    def alive(self) -> bool:
        if self.force_dead:
            return False
        return self.proc is None or self.proc.poll() is None

    def terminate(self) -> None:
        """None-safe terminate (proc is None while spawn-queued). A chip
        holder is SIGKILLed: libtpu's own SIGTERM handler takes ~8 s to
        let go of the chip (measured on a v5e), and a successor leased
        the same chip fails on the device lock meanwhile."""
        if self.chips.get(TPU):
            self.hard_kill()
        elif self.proc is not None:
            try:
                self.proc.terminate()
            except Exception:
                pass

    def hard_kill(self) -> None:
        """SIGKILL — for workers that ignore SIGTERM (e.g. wedged in a
        native collective holding the GIL, where the Python-level signal
        handler never gets to run)."""
        if self.proc is not None:
            try:
                kill = getattr(self.proc, "kill", None)
                if kill is not None:
                    kill()
                elif getattr(self.proc, "pid", None):
                    os.kill(self.proc.pid, 9)
            except Exception:
                pass

    def mark_failed(self) -> None:
        """A launch that will never produce a process: flips `alive` to
        False so liveness watchers (actor resource release) resolve."""
        if self.proc is None:
            self.proc = _NeverLaunched()


class NodeAgent:
    def __init__(
        self,
        node_id: str,
        session_dir: str,
        store_dir: str,
        head_host: str,
        head_port: int,
        resources: Dict[str, float],
        labels: Optional[Dict[str, str]] = None,
        object_store_memory: Optional[int] = None,
    ):
        self.node_id = node_id
        # per-boot incarnation: strictly increases across restarts of an
        # agent under the same node_id, so the head can fence a dead
        # incarnation while letting a fresh boot rejoin (ns resolution —
        # two boots within one tick would defeat the fence)
        self.incarnation = time.time_ns()
        self.session_dir = session_dir
        self.head_host = head_host
        self.head_port = head_port
        self.unix_path = os.path.join(session_dir, "sockets", f"agent-{node_id[:12]}.sock")
        os.makedirs(os.path.dirname(self.unix_path), exist_ok=True)
        self.store = StoreDirectory(store_dir, capacity=object_store_memory)
        self.store_dir = store_dir
        accel_ids: Dict[str, list] = {}
        for name in ("TPU", "GPU"):
            if resources.get(name):
                accel_ids[name] = list(range(int(resources[name])))
        self.resources = NodeResources(ResourceSet(resources), labels, accel_ids)
        self.server = RpcServer("agent")
        self.tcp_port = 0
        self.head = AsyncRpcClient()
        self.pool = ConnectionPool()
        self.cluster_view: Dict[str, Dict] = {}

        # worker pool state
        self.workers: Dict[str, WorkerHandle] = {}
        self.idle_workers: List[WorkerHandle] = []
        self.leases: Dict[str, WorkerHandle] = {}
        # actor_id -> hosting worker: kill/lookup without scanning the
        # whole worker table (O(1) at 1000+ live actors)
        self.workers_by_actor: Dict[str, WorkerHandle] = {}
        self.max_workers = int(resources.get("CPU", 1)) or 1
        if CONFIG.num_workers_soft_limit:
            self.max_workers = CONFIG.num_workers_soft_limit
        self._starting_workers = 0
        # warm pool bookkeeping (ISSUE 10): pristine spawns in flight (so
        # the refill loop needn't scan self.workers), hit/miss counters,
        # and the forkserver death-ledger read offset (pids reaped by the
        # forkserver's SIGCHLD handler — the agent's kill(pid, 0) probe
        # cannot see those deaths once the pid is recycled)
        self._spawning_plain = 0
        # set on teardown: the warm-pool refill loop must stop forking (a
        # refill racing shutdown can respawn the forkserver AFTER the
        # terminate sweep captured its pid — a leaked daemon)
        self._closing = False
        self._pool_hits = 0
        self._pool_misses = 0
        self._pool_refills = 0
        self._pool_reaped = 0
        # predictive demand-paged refill (ISSUE 11): actor starts that
        # miss the warm pool park here for the next pool registration
        # (instead of each cold-forking), and the refill burst is sized
        # from the StartActor(Batch) demand seen inside the window —
        # not one fork per tick
        self._pool_waiters: deque = deque()
        self._demand_hits = 0
        self._demand_events: deque = deque()  # (monotonic, n)
        self._pid_handles: Dict[int, WorkerHandle] = {}
        self._death_ledger_pos = 0
        # batched control-RPC state: queued worker ActorReady reports
        # (flushed as ONE head RPC per window) + batch-size histograms
        self._ready_queue: List[Tuple[Dict, asyncio.Future]] = []
        self._ready_flush_armed = False
        self._ready_batch_hist: Dict[str, int] = {}
        self._lease_batch_hist: Dict[str, int] = {}
        # spawn admission (reference: maximum_startup_concurrency):
        # requests queue here; at most STARTUP_CONCURRENCY are between
        # fork and registration at once
        self._spawn_queue: deque = deque()
        self._launching_workers = 0
        # warm-template forkserver (worker_forkserver.py): plain workers
        # fork from a pre-imported template (~20ms) instead of a cold
        # interpreter launch (~350ms); container/conda workers still use
        # Popen (they need a different command line)
        self._forkserver_proc: Optional[subprocess.Popen] = None
        self._forkserver_sock = os.path.join(
            session_dir, "sockets", f"fs-{node_id[:12]}.sock")
        self._lease_counter = 0
        self._pending_leases: List[Dict] = []  # queued lease requests

        # transient spill ledger: demands redirected to a remote node in
        # the last ~2s, counted against its advertised availability so a
        # burst of simultaneous lease requests doesn't all pick the same
        # least-utilized node off the same stale gossip view
        self._recent_spills: Dict[str, List[Tuple[float, ResourceSet]]] = {}

        # object plane
        self._object_waits: Dict[str, List[asyncio.Future]] = {}
        self._pulls_inflight: Dict[str, asyncio.Task] = {}
        # cancelled pulls whose cleanup (stripe teardown + store abort) is
        # still running; a NEW pull of the same object must wait for ALL
        # of them or an old abort unlinks the new transfer's unsealed
        # allocation (list: rapid waiter churn can park several)
        self._pulls_draining: Dict[str, List[asyncio.Task]] = {}
        # hex -> monotonic stamp of the LAST waiter departure; only the
        # reap timer matching the current stamp may cancel, so the grace
        # window always runs full length from the latest detach
        self._pull_orphan_stamp: Dict[str, float] = {}
        # serve-side view cache: see _fetch_object_chunk
        self._serve_view_cache: "OrderedDict[str, list]" = OrderedDict()
        self.pulls = PullManager(self)
        # zero-copy array puts sealed on this node (device object plane)
        self._zero_copy_puts = 0

        # object ownership ledger (ISSUE 15): hex -> {owner addr, creating
        # task, sealed_at} recorded from ObjectSealed/WaitObjects; pruned
        # on free and whenever a scan observes the object gone from the
        # store. Feeds GetObjectRefs and the leak watchdog.
        self._object_owners: Dict[str, Dict] = {}
        # driver processes registered on this node (worker_id -> {addr,
        # pid}); workers are in self.workers, but the DRIVER owns most
        # objects and must be introspectable too. Pruned on disconnect.
        self._driver_clients: Dict[str, Dict] = {}
        # leak watchdog state: first-seen stamps of leak candidates and
        # the last scan's confirmed suspects (CLI/metrics read these)
        self._leak_candidates: Dict[str, float] = {}
        self._leak_suspects: List[Dict] = []
        self._leak_scans = 0
        # repair hook (ISSUE 17): store copies freed after a graduated
        # owner_unreachable / zero_refs verdict
        self._leak_repairs = 0

        # placement groups: (pg_id, bundle_index) -> reserved ResourceSet
        self._pg_bundles: Dict[Tuple[str, int], ResourceSet] = {}
        self._pg_available: Dict[Tuple[str, int], ResourceSet] = {}
        # instance ids each bundle reserved and has not handed to a tenant
        self._pg_chips: Dict[Tuple[str, int], Dict[str, list]] = {}

        self._resources_dirty = True
        self._register_routes()

    # ------------------------------------------------------------------ boot
    async def start(self) -> None:
        from ray_tpu._private.event import init_event_log, report_event

        init_event_log(self.session_dir, f"agent_{self.node_id[:8]}")
        report_event("INFO", "NODE_STARTED",
                     f"node agent {self.node_id[:12]} starting",
                     node_id=self.node_id)
        # flight recorder (ISSUE 14): pull / broadcast / spill / actor-start
        # spans ride the same crash-durable ring workers use
        _events.configure(self.session_dir, "agent")
        await self.server.start_unix(self.unix_path)
        self.tcp_port = await self.server.start_tcp("0.0.0.0", 0)
        self.server.set_disconnect_handler(self._on_disconnect)
        await self._connect_head()
        spawn_tracked(self._resource_report_loop(), "agent-resource-report")
        spawn_tracked(self._worker_reaper_loop(), "agent-worker-reaper")
        spawn_tracked(self._node_stats_loop(), "agent-node-stats")
        spawn_tracked(self._head_watchdog_loop(), "agent-head-watchdog")
        if float(CONFIG.object_leak_scan_interval_s) > 0:
            # default-off: the watchdog only exists when the knob arms it
            spawn_tracked(self._leak_watchdog_loop(), "agent-leak-watchdog")
        if _events.REC.enabled:
            spawn_tracked(self._events_flush_loop(), "agent-events-flush")
        if os.environ.get("RAY_TPU_LOG_TO_DRIVER", "1") != "0":
            from ray_tpu._private.log_monitor import LogMonitor

            async def publish(channel, message):
                await self.head.call("Publish",
                                     {"channel": channel, "message": message},
                                     timeout=CONFIG.control_rpc_timeout_s)

            monitor = LogMonitor(os.path.join(self.session_dir, "logs"),
                                 self.node_id, publish)
            spawn_tracked(monitor.run(), "agent-log-monitor")
        if os.environ.get("RAY_TPU_MEMORY_MONITOR", "1") != "0":
            from ray_tpu._private.memory_monitor import (
                MemoryMonitor,
                OomKiller,
            )

            def list_leases():
                return [
                    {"lease": lid, "worker": w,
                     "retriable": getattr(w, "lease_retriable", True),
                     "owner": getattr(w, "lease_owner", ""),
                     "start": getattr(w, "lease_start", 0.0)}
                    for lid, w in self.leases.items()
                    if w.alive and not w.is_actor
                ]

            def kill(victim):
                from ray_tpu._private.event import report_event

                w = victim["worker"]
                report_event("WARNING", "OOM_KILL",
                             f"killing worker {w.worker_id[:12]} under "
                             "memory pressure",
                             worker_id=w.worker_id, node_id=self.node_id)
                try:
                    w.terminate()  # owner sees the failure and retries
                except Exception:
                    pass

            threshold = float(
                os.environ.get("RAY_TPU_MEMORY_USAGE_THRESHOLD", "0.95"))
            self.oom_killer = OomKiller(
                MemoryMonitor(usage_threshold=threshold), list_leases, kill)
            spawn_tracked(self.oom_killer.run(), "agent-oom-killer")
        spawn_tracked(self._prestart(), "agent-prestart")
        spawn_tracked(self._warm_pool_loop(), "agent-warm-pool")

    async def _events_flush_loop(self) -> None:
        """Batch-flush this agent's flight-recorder ring to the head
        (extending the ReportTaskEvents path the way driver/worker
        processes do). The ring itself stays the crash-durable copy."""
        rec = _events.REC
        while not self._closing:
            await asyncio.sleep(max(0.5, CONFIG.task_event_flush_interval_s))
            if rec.counter == rec.flushed:
                continue
            spans = rec.drain()
            try:
                await self.head.call(
                    "ReportTaskEvents",
                    {"node_id": self.node_id, "spans": spans,
                     "role": "agent", "pid": os.getpid(),
                     "ring": rec.stats()},
                    timeout=CONFIG.control_rpc_timeout_s)
            except Exception:
                pass  # head mid-bounce: spans stay readable in the ring

    async def aclose_clients(self) -> None:
        """Await every outbound client's read loop (head + the per-peer
        control/data connection pool) so shutdown leaves no pending task."""
        self._closing = True
        await self.pool.aclose_all()
        try:
            await self.head.aclose()
        except Exception:
            pass
        try:
            await self.server.close()
        except Exception:
            pass

    def teardown_processes(self) -> None:
        """Reap everything this agent spawned (workers, forkserver, and —
        via the session registry — grandchildren in foreign pgids), and
        return when they are gone from the process table. The
        agent is the fate-share supervisor for its node: this runs on
        SIGTERM, on head-gone give-up, and when the spawning driver dies,
        so no daemon outlives the session: leaked daemons starve whatever
        runs next on the machine.

        In order: the workers die and are waited for while the forkserver,
        their parent and the only process that can reap them, lives; a
        forkserver killed in the same sweep hands a chip's holder that is
        still letting go of the device to pid 1 (ledger, PRs 47 and 49).
        The registry knows the workers retired a moment ago (a lease
        returned, a driver gone), which ``self.workers`` has forgotten."""
        self._closing = True
        workers = list(self.workers.values())
        for w in workers:
            if w.chips.get(TPU):
                w.hard_kill()  # never SIGTERM: `WorkerHandle.terminate`
        for reap in (
                lambda: lifecycle.terminate_tree([w.proc for w in workers]),
                lambda: lifecycle.reap_session(
                    self.session_dir, node_id=self.node_id,
                    sigterm_timeout_s=1.0, roles=("worker",)),
                lambda: lifecycle.terminate_tree([self._forkserver_proc]),
                lambda: lifecycle.reap_session(
                    self.session_dir, node_id=self.node_id,
                    sigterm_timeout_s=1.0)):
            try:
                reap()
            except Exception:
                pass

    def _register_routes(self) -> None:
        r = self.server.add_handler
        # local clients
        r("RegisterClient", self._register_client)
        r("RequestWorkerLease", self._request_worker_lease)
        r("RequestWorkerLeaseBatch", self._request_worker_lease_batch)
        r("ReturnWorker", self._return_worker)
        r("ReportActorReady", self._report_actor_ready)
        r("GetWorkerPoolStats", self._get_worker_pool_stats)
        r("ObjectSealed", self._object_sealed)
        r("WaitObjects", self._wait_objects)
        r("FreeObjects", self._free_objects)
        r("PinObject", self._pin_object)
        r("UnpinObject", self._unpin_object)
        r("GetStoreStats", self._get_store_stats)
        r("GetPullStats", self._get_pull_stats)
        r("GetNodeInfo", self._get_node_info)
        r("ListWorkers", self._list_workers)
        r("ListEvents", self._list_events)
        r("GetNodeStats", self._get_node_stats)
        r("ListStoreObjects", self._list_store_objects)
        r("GetObjectRefs", self._get_object_refs)
        r("SetResource", self._set_resource)
        r("RestoreSpilled", self._restore_spilled)
        # remote agents
        r("FetchObjectMeta", self._fetch_object_meta)
        r("FetchObjectChunk", self._fetch_object_chunk)
        r("Ping", self._ping)

    async def _ping(self, conn: Connection, p) -> Dict:
        """Liveness probe target (idle-deadline monitors, chaos tooling)."""
        return {"ok": True, "node_id": self.node_id,
                "incarnation": self.incarnation}

    async def _prestart(self) -> None:
        """Initial warm-pool fill: burst-fork up to the warm target (the
        spawn admission queue still caps concurrent boots); the warm-pool
        loop maintains the level afterwards with rate-limited refills.
        With warm leasing disabled, keep the historical prestart of
        min(max_workers, num_cpus) plain workers."""
        if self.warm_lease_enabled:
            target = self.WARM_TARGET
        else:
            target = min(self.max_workers,
                         int(self.resources.total.get("CPU")) or 1)
        for _ in range(target):
            self._spawn_worker(pool_fill=True)

    # ------------------------------------------------------ warm worker pool
    @property
    def WARM_TARGET(self) -> int:
        """Pre-warmed pool size the refill loop maintains (ISSUE 10).
        0 = auto (max(2, num_cpus)); negative config disables warm
        leasing entirely (cold fork per actor, the pre-pool behavior)."""
        t = int(CONFIG.worker_pool_warm_target)
        if t < 0:
            return 0
        if t == 0:
            return max(2, int(self.resources.total.get("CPU") or 1))
        return t

    @property
    def warm_lease_enabled(self) -> bool:
        return int(CONFIG.worker_pool_warm_target) >= 0

    def _warm_idle_count(self) -> int:
        return sum(1 for w in self.idle_workers
                   if w.env_key is None and w.alive and not w.is_actor)

    async def _warm_pool_loop(self) -> None:
        """Background refill: keep ``WARM_TARGET`` pristine workers parked
        (booted through registration, before any actor-class unpickle),
        at most one fork per ``worker_pool_refill_interval_ms`` so a
        drained pool refills without starving the burst that drained it
        (reference: worker_pool.h prestart + maximum_startup_concurrency)."""
        while True:
            await asyncio.sleep(
                max(CONFIG.worker_pool_refill_interval_ms, 5) / 1000.0)
            if not self.warm_lease_enabled or self._closing:
                continue
            try:
                self._consume_death_ledger()
            except Exception:
                pass
            # predictive refill (ISSUE 11): deficit = live waiters +
            # warm floor − parked − mid-boot; the burst events from
            # _note_actor_demand already pre-forked toward the batch
            # window, so this tick only covers the floor and
            # stragglers (a fork that died, an expired waiter)
            self._refill_to_demand(include_floor=True)

    def _consume_death_ledger(self) -> None:
        """Apply the forkserver's SIGCHLD death ledger: a warm worker that
        died between fork and first lease has no agent connection to drop
        and its pid may already be recycled — without the ledger a dead
        (or foreign) pid could be leased. Cheap when nothing died (one
        stat per call)."""
        path = self._forkserver_sock + ".deaths"
        try:
            if os.path.getsize(path) <= self._death_ledger_pos:
                return
            with open(path, "r") as f:
                f.seek(self._death_ledger_pos)
                data = f.read()
                self._death_ledger_pos = f.tell()
        except OSError:
            return
        for line in data.splitlines():
            try:
                pid = int(line)
            except ValueError:
                continue
            handle = self._pid_handles.get(pid)
            if handle is None or handle.worker_id not in self.workers:
                continue
            handle.force_dead = True
            spawn_tracked(
                self._handle_worker_exit(
                    handle, "reaped by forkserver (death ledger)"),
                "agent-ledger-exit")

    def _note_actor_demand(self, n: int) -> None:
        """A StartActor(Batch) frame just landed: record the demand and
        pre-fork toward it NOW — by the time the entries clear resource
        admission, their workers are already booting through the
        admission queue (the 1-fork/tick pacing this replaces left
        hit_ratio at 0.17 under a burst of 200, round 10's actor_scale
        run on a CPU box)."""
        if n > 0:
            self._demand_events.append((time.monotonic(), n))
            # prune HERE, not just in the stats read (the only other
            # caller): a long-lived agent serving millions of creates
            # with nobody polling stats must not grow this unbounded
            self._recent_demand()
        self._refill_to_demand(extra_demand=n)

    def _recent_demand(self) -> int:
        window = float(CONFIG.worker_pool_demand_window_s)
        now = time.monotonic()
        while self._demand_events and \
                now - self._demand_events[0][0] > window:
            self._demand_events.popleft()
        return sum(n for _t, n in self._demand_events)

    def _refill_to_demand(self, extra_demand: int = 0,
                          include_floor: bool = False) -> None:
        """Fork pool-fill workers up to the observed shortfall: live
        waiters + fresh batch demand, minus what is already parked or
        mid-boot. The warm FLOOR is included only from the periodic
        loop tick — adding it per StartActorBatch would re-fork the
        floor once per frame of a burst (measured: 546 forks for 400
        actors, every extra fork stealing boot CPU from the burst on a
        2-core box). The spawn admission queue still bounds concurrent
        boots; this only sizes the pipeline."""
        if not self.warm_lease_enabled or self._closing:
            return
        # shed settled waiters (timed-out futures from re-arm windows):
        # without this the deque only drains when a registration pops
        # through it, which is exactly what ISN'T happening when
        # waiters time out
        while self._pool_waiters and self._pool_waiters[0].done():
            self._pool_waiters.popleft()
        deficit = (extra_demand
                   + sum(1 for f in self._pool_waiters if not f.done())
                   + (self.WARM_TARGET if include_floor else 0)
                   - self._warm_idle_count()
                   - self._spawning_plain)
        cap = int(CONFIG.worker_pool_refill_burst_max)
        if cap > 0:
            deficit = min(deficit, cap)
        for _ in range(max(0, deficit)):
            self._pool_refills += 1
            self._spawn_worker(pool_fill=True)

    def _offer_pool_worker(self, handle: WorkerHandle) -> bool:
        """Hand a just-available pristine worker to the oldest live
        pool waiter (a missed actor start parked by demand paging).
        True = consumed; False = caller parks it idle as before."""
        if handle.is_actor or handle.leased_to is not None or \
                handle.env_key is not None:
            return False
        while self._pool_waiters:
            fut = self._pool_waiters.popleft()
            if fut.done():
                continue  # waiter timed out and cold-forked meanwhile
            fut.set_result(handle)
            return True
        return False

    async def _wait_pool_worker(self) -> Optional[WorkerHandle]:
        """Demand-paged miss path: park for the next pool registration
        instead of cold-forking a dedicated process. The wait window
        EXTENDS while pool-fill spawns are still in flight — under a
        saturated burst the pre-forked worker for the queue tail
        legitimately arrives after a flat window, and a timeout there
        cold-forks a DUPLICATE that steals boot CPU from the very
        pipeline the waiter depends on (measured: a 20 s cliff turned
        92/400 starts into duplicate forks and halved the burst rate).
        Hard-capped regardless, so a wedged forkserver still degrades
        to the cold fork (never a failure mode)."""
        fut = asyncio.get_running_loop().create_future()
        self._pool_waiters.append(fut)
        self._refill_to_demand()
        window = float(CONFIG.worker_pool_wait_s)
        deadline = time.monotonic() + 10 * window
        remaining = window
        while True:
            try:
                handle = await asyncio.wait_for(
                    fut, timeout=max(0.05, remaining))
                break
            except asyncio.TimeoutError:
                if self._spawning_plain <= 0 or \
                        time.monotonic() > deadline or self._closing:
                    return None
                # workers are still owed to the pool: re-arm a fresh
                # future (the timed-out one is poisoned for set_result
                # — and removed so it cannot accumulate as deque junk)
                try:
                    self._pool_waiters.remove(fut)
                except ValueError:
                    pass
                fut = asyncio.get_running_loop().create_future()
                self._pool_waiters.append(fut)
                remaining = window
        # paranoia at handout: the ledger may have caught its death
        # between registration and this wakeup
        if not handle.alive or (handle.conn is not None
                                and handle.conn.closed):
            return None
        return handle

    def _lease_warm_worker(self, unused_only: bool = False
                           ) -> Optional[WorkerHandle]:
        """Pop a live pristine warm worker for an actor start, with a
        liveness check on handout (alive pid, registered, connection not
        mid-close, not in the death ledger)."""
        if not self.warm_lease_enabled:
            return None
        try:
            self._consume_death_ledger()
        except Exception:
            pass
        return self._pop_idle_worker(None, unused_only=unused_only)

    # ------------------------------------------------------------ head link
    async def _connect_head(self) -> None:
        await self.head.connect_tcp(self.head_host, self.head_port)
        self.head.set_push_handler(self._on_head_push)
        # bounded: a one-way partition eats the request without an RST, and
        # an unbounded call would wedge the watchdog's reconnect loop on
        # its very first attempt (it could then never deliver a fence
        # verdict after the partition heals)
        reply = await self.head.call(
            "RegisterNode",
            {
                "node_id": self.node_id,
                "incarnation": self.incarnation,
                "addr": {"host": "127.0.0.1", "port": self.tcp_port},
                "resources": self.resources.to_wire(),
                # the actors this node ACTUALLY still hosts: a restarted
                # head reconciles its restored (RECOVERING) actor table
                # against this list — present means claimed-alive, absent
                # means the worker died during the outage
                "actors": [w.actor_id for w in self.workers.values()
                           if w.is_actor and w.actor_id and w.alive],
            },
            timeout=max(CONFIG.head_ping_timeout_s * 2, 5.0),
        )
        if reply.get("fenced"):
            raise NodeFencedError(
                f"node {self.node_id[:12]} incarnation {self.incarnation} "
                "was fenced by the head")
        CONFIG.apply_cluster_config(reply.get("cluster_config", {}))
        self.cluster_view = reply.get("cluster_view", {})
        self._resources_dirty = True

    def _fenced_suicide(self) -> None:
        """The head fenced us: tear down every process this node spawned
        (workers holding zombie leases, the forkserver) and exit. After a
        healed partition this is what converges the lifecycle pid
        registry to zero instead of leaving a shadow cluster."""
        from ray_tpu._private.event import report_event

        try:
            report_event("ERROR", "NODE_FENCED_EXIT",
                         f"node {self.node_id[:12]} fenced by head; "
                         "terminating",
                         node_id=self.node_id,
                         incarnation=self.incarnation)
        except Exception:
            pass
        self.teardown_processes()
        try:
            lifecycle.unregister_process(self.session_dir, os.getpid())
        except Exception:
            pass
        os._exit(1)

    async def _head_watchdog_loop(self) -> None:
        """Survive a head restart (reference: GCS fault tolerance —
        NotifyGCSRestart + raylet resubscribe, node_manager.proto:364):
        ping the head; on failure reconnect with backoff and re-register
        under the same node_id so leases/actors on this node carry over.

        If the head stays gone past ``agent_head_gone_exit_s``, the agent
        shuts itself (and its workers) down: an unreachable head means the
        cluster is dead, and immortal orphaned agents accumulate into a
        box-wide CPU leak (observed: a killed test run left 40+ agents
        idling at ~1%% CPU each — reference parity: raylets exit when the
        GCS declares them dead, node_manager.cc HandleUnexpectedDisconnect)."""
        give_up_s = float(CONFIG.agent_head_gone_exit_s)
        while True:
            await asyncio.sleep(CONFIG.head_watchdog_period_s)
            try:
                await asyncio.wait_for(
                    self.head.call("Ping", {}),
                    timeout=CONFIG.head_ping_timeout_s)
                continue
            except Exception:
                pass
            # decorrelated jitter: after a head bounce every agent's
            # retries spread across the interval instead of arriving in
            # synchronized waves at the recovering head
            backoff = DecorrelatedJitterBackoff(base_s=0.2, cap_s=2.0)
            down_since = time.monotonic()
            while True:
                try:
                    await self.head.aclose()
                except Exception:
                    pass
                try:
                    # reconnect in place: connect_tcp replaces the broken
                    # stream and restarts the read loop on self.head
                    await self._connect_head()
                    break
                except NodeFencedError:
                    # the cluster declared this incarnation dead while we
                    # were partitioned; self-terminate (no zombie leases)
                    self._fenced_suicide()
                except Exception:
                    if time.monotonic() - down_since > give_up_s:
                        _events.REC.dump_local("head_gone_exit")
                        self.teardown_processes()
                        os._exit(1)
                    await asyncio.sleep(backoff.next_delay())

    async def _on_head_push(self, method: str, payload: Any) -> None:
        if method == "ClusterView":
            self.cluster_view = payload
            await self._drain_pending_leases()
        elif method == "StartActor":
            self._note_actor_demand(1)
            await self._start_actor(payload)
        elif method == "StartActorBatch":
            # one frame per node per CreateActorBatch: each entry gets its
            # own task — _start_actor can legitimately await resource
            # capacity, and one starved entry must not wedge its siblings.
            # The batch size IS the demand window: pre-fork toward it now
            # so workers boot while entries clear admission (ISSUE 11).
            self._note_actor_demand(len(payload["items"]))
            for item in payload["items"]:
                spawn_tracked(self._start_actor(item), "agent-start-actor")
        elif method == "KillActorWorker":
            self._kill_actor_worker(payload["actor_id"])
        elif method == "PreparePGBundle":
            ok = self._prepare_pg_bundle(payload)
            await self.head.call(
                "Publish",
                {"channel": payload["reply_channel"], "message": {"ok": ok}},
                timeout=CONFIG.control_rpc_timeout_s,
            )
        elif method == "ReturnPGBundle":
            self._return_pg_bundle(payload)
        elif method == "NodeRemoved":
            self._on_peer_node_removed(payload)
        elif method == "Pub":
            pass
        elif method == "Drain":
            pass

    def _on_peer_node_removed(self, payload: Dict) -> None:
        """Fail-fast on a peer's death verdict: purge it from the gossip
        view immediately (spillback must stop targeting it) and drop the
        cached control/data channels so every in-flight RPC to it — chunk
        fetches mid-pull, spilled lease requests — fails NOW instead of
        waiting out a 60 s chunk deadline on a socket a partition will
        never reset."""
        node_id = payload.get("node_id")
        if node_id:
            self.cluster_view.pop(node_id, None)
        addr = payload.get("addr") or {}
        if addr.get("host") is not None and addr.get("port") is not None:
            self.pulls.on_peer_removed(addr)  # drops ctrl+data channels
            # a dead peer is no longer a remote-tier restore source
            self.store.forget_remote_source(addr)

    async def _resource_report_loop(self) -> None:
        """Versioned delta gossip (reference: ray_syncer.h:88 — versioned
        per-node RESOURCE_VIEW snapshots over bidi streams). A full snapshot
        goes out only when the node's view changed; unchanged ticks send a
        tiny heartbeat frame, so head ingress per tick is O(changed nodes)
        plus O(n) constant-size liveness probes."""
        period = max(CONFIG.gossip_period_ms, 50) / 1000
        last_sent: Optional[Dict] = None
        version = 0
        while True:
            await asyncio.sleep(period)
            dirty = self._resources_dirty
            self._resources_dirty = False
            snapshot = {
                "resources": self.resources.to_wire(),
                "pending": [r["resources"].to_wire()
                            for r in self._pending_leases],
            }
            try:
                if dirty or snapshot != last_sent:
                    version += 1
                    await self.head.call(
                        "UpdateResources",
                        {"node_id": self.node_id, "v": version, **snapshot},
                        timeout=CONFIG.control_rpc_timeout_s)
                    last_sent = snapshot
                else:
                    reply = await self.head.call(
                        "UpdateResources",
                        {"node_id": self.node_id, "hb": True, "v": version},
                        timeout=CONFIG.control_rpc_timeout_s)
                    if reply and reply.get("resync"):
                        # the head's applied version disagrees with ours
                        # (restart / lost report): next tick sends full
                        last_sent = None
            except Exception:
                # head unreachable or restarted: resend full on recovery
                last_sent = None

    # ---------------------------------------------------------- worker pool
    @property
    def STARTUP_CONCURRENCY(self) -> int:
        cap = CONFIG.worker_startup_concurrency
        if cap > 0:
            return cap
        return max(2, int(self.resources.total.get("CPU") or 1))

    def _spawn_worker(self, actor_spec: Optional[Dict] = None,
                      container: Optional[Dict] = None,
                      conda_prefix: Optional[str] = None,
                      env_key: Optional[str] = None,
                      pool_fill: bool = False) -> WorkerHandle:
        """Admission-queued spawn: a burst of requests (1000 actors at
        once) must not fork 1000 interpreters simultaneously — that starves
        the node's cores until the head's health checks declare it dead.
        At most STARTUP_CONCURRENCY processes are between fork and
        registration at any moment (reference: worker_pool.h
        maximum_startup_concurrency = num_cpus)."""
        worker_id = os.urandom(16).hex()
        handle = WorkerHandle(worker_id, proc=None)
        handle.env_key = env_key
        self.workers[worker_id] = handle
        self._starting_workers += 1
        if pool_fill:
            # pool-fill spawn (prestart / warm refill): counts toward the
            # warm level until it registers (or dies trying). Cold actor
            # forks and demand task spawns do NOT count — they never park
            # in the pool, and counting them would zero the refill
            # deficit for exactly as long as a miss burst lasts.
            handle.pending_plain = True
            self._spawning_plain += 1
        self._spawn_queue.append(
            (handle, actor_spec, container, conda_prefix, env_key))
        self._workers_spawned = getattr(self, "_workers_spawned", 0) + 1
        self._kick_spawner()
        return handle

    def _plain_spawn_done(self, handle: WorkerHandle) -> None:
        """A pristine spawn registered or died: it no longer counts as a
        warm-pool fill in flight (exactly-once via the flag reset)."""
        if getattr(handle, "pending_plain", False):
            handle.pending_plain = False
            self._spawning_plain = max(0, self._spawning_plain - 1)

    def _kick_spawner(self) -> None:
        while (self._spawn_queue
               and self._launching_workers < self.STARTUP_CONCURRENCY):
            (handle, actor_spec, container, conda_prefix,
             env_key) = self._spawn_queue.popleft()
            if handle.worker_id not in self.workers:  # cancelled meanwhile
                self._starting_workers = max(0, self._starting_workers - 1)
                continue
            self._launching_workers += 1
            handle.launching = True
            if container or conda_prefix:
                try:
                    self._launch_worker(handle, container, conda_prefix,
                                        env_key)
                except Exception:
                    self._launching_workers -= 1
                    handle.launching = False
                    self._starting_workers = max(0,
                                                 self._starting_workers - 1)
                    handle.mark_failed()
                    self.workers.pop(handle.worker_id, None)
            else:
                spawn_tracked(self._launch_via_forkserver(handle, env_key),
                              "agent-forkserver-launch")

    async def _launch_via_forkserver(self, handle: WorkerHandle,
                                     env_key: Optional[str]) -> None:
        try:
            pid = await self._forkserver_spawn(handle)
        except Exception:
            pid = None
        if pid:
            handle.proc = _ForeignProc(pid)
            handle.launched_at = time.monotonic()
            handle.spawn_time = time.monotonic()
            self._pid_handles[pid] = handle
            lifecycle.register_process(self.session_dir, "worker", pid,
                                       self.node_id)
            return
        # template unavailable/broken: cold-launch fallback (never during
        # teardown — a shutdown-raced spawn would leak past the sweep)
        try:
            if self._closing:
                raise RuntimeError("agent closing")
            self._launch_worker(handle, None, None, env_key)
        except Exception:
            self._launching_workers = max(0, self._launching_workers - 1)
            handle.launching = False
            self._starting_workers = max(0, self._starting_workers - 1)
            handle.mark_failed()
            self.workers.pop(handle.worker_id, None)
            # the freed slot must pull the next queued spawn or a burst
            # whose launches all fail would strand the queue forever
            self._kick_spawner()

    def _worker_ray_env(self, worker_id: str) -> Dict[str, str]:
        """The one authoritative worker-bootstrap variable set (every
        launch path — forkserver, Popen, container, conda — builds on
        this; divergence here means divergent worker environments).
        RAY_TPU_PARENT_PID designates this agent as the worker's
        fate-share supervisor (lifecycle.fate_share_with_parent)."""
        return {
            "RAY_TPU_WORKER_ID": worker_id,
            "RAY_TPU_AGENT_SOCK": self.unix_path,
            "RAY_TPU_NODE_ID": self.node_id,
            "RAY_TPU_SESSION_DIR": self.session_dir,
            "RAY_TPU_STORE_DIR": self.store_dir,
            "RAY_TPU_HEAD_ADDR": f"{self.head_host}:{self.head_port}",
            "RAY_TPU_PARENT_PID": str(os.getpid()),
        }

    def _worker_env(self, worker_id: str) -> Dict[str, str]:
        from ray_tpu._private.config import keep_off_accelerator

        env = dict(os.environ)
        env.update(self._worker_ray_env(worker_id))
        keep_off_accelerator(env)
        return env

    async def _forkserver_spawn(self, handle: WorkerHandle) -> Optional[int]:
        """Ask the warm template to fork a worker; returns the child pid
        or None when the template can't serve (caller cold-launches)."""
        import json as _json

        if self._closing:
            return None
        if self._forkserver_proc is None or \
                self._forkserver_proc.poll() is not None:
            from ray_tpu._private.config import (
                keep_off_accelerator, whole_malloc_heaps)

            env = dict(os.environ)
            keep_off_accelerator(env)
            whole_malloc_heaps(env)   # the template's, so every fork's
            try:
                os.unlink(self._forkserver_sock + ".ready")
            except FileNotFoundError:
                pass
            log_dir = os.path.join(self.session_dir, "logs")
            os.makedirs(log_dir, exist_ok=True)
            with open(os.path.join(log_dir, "forkserver.log"), "ab") as lg:
                env["RAY_TPU_SESSION_DIR"] = self.session_dir
                env["RAY_TPU_NODE_ID"] = self.node_id
                env["RAY_TPU_PARENT_PID"] = str(os.getpid())
                self._forkserver_proc = subprocess.Popen(
                    [sys.executable, "-m",
                     "ray_tpu._private.worker_forkserver",
                     self._forkserver_sock],
                    env=env, stdout=lg, stderr=lg, start_new_session=True)
            lifecycle.register_process(self.session_dir, "forkserver",
                                       self._forkserver_proc.pid,
                                       self.node_id)
            # the fresh forkserver unlinks + recreates its death ledger:
            # a stale offset would silently skip (or mid-line misparse)
            # every death it reports from now on
            self._death_ledger_pos = 0
        for _ in range(200):  # template warms up once (~0.5s)
            if os.path.exists(self._forkserver_sock + ".ready"):
                break
            if self._forkserver_proc.poll() is not None:
                return None
            await asyncio.sleep(0.05)
        else:
            return None
        log_dir = os.path.join(self.session_dir, "logs")
        wid = handle.worker_id
        req = {
            "env": self._worker_env(wid),
            "log_out": os.path.join(log_dir, f"worker-{wid[:12]}.out"),
            "log_err": os.path.join(log_dir, f"worker-{wid[:12]}.err"),
        }
        writer = None
        try:
            reader, writer = await asyncio.open_unix_connection(
                self._forkserver_sock)
            writer.write((_json.dumps(req) + "\n").encode())
            await writer.drain()
            line = await asyncio.wait_for(reader.readline(), 30)
            rep = _json.loads(line)
            return rep.get("pid")
        except Exception:
            return None
        finally:
            # the forkserver serves connections serially — a leaked open
            # connection (timeout/exception path) would stall every
            # subsequent warm-fork request behind its recv loop
            if writer is not None:
                try:
                    writer.close()
                except Exception:
                    pass

    def _spawn_slot_freed(self, handle: WorkerHandle) -> None:
        """A launching worker registered or died: free its startup slot."""
        if getattr(handle, "launching", False):
            handle.launching = False
            self._launching_workers = max(0, self._launching_workers - 1)
            self._kick_spawner()

    def _launch_worker(self, handle: WorkerHandle,
                       container: Optional[Dict] = None,
                       conda_prefix: Optional[str] = None,
                       env_key: Optional[str] = None) -> None:
        worker_id = handle.worker_id
        log_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        out = open(os.path.join(log_dir, f"worker-{worker_id[:12]}.out"), "ab")
        err = open(os.path.join(log_dir, f"worker-{worker_id[:12]}.err"), "ab")
        ray_env = self._worker_ray_env(worker_id)
        if container:
            # container runtime_env: the worker process starts INSIDE
            # podman/docker with the session dir (unix socket), object
            # store, and the ray_tpu package bind-mounted (reference:
            # _private/runtime_env/container.py prepending `podman run`)
            from ray_tpu.runtime_env.container import (
                worker_container_command)

            # same pin as the host path (only ray_env crosses into the
            # container)
            from ray_tpu._private.config import keep_off_accelerator

            keep_off_accelerator(ray_env)
            cmd = worker_container_command(
                container, self.session_dir, self.store_dir, ray_env)
            env = dict(os.environ)
        elif conda_prefix:
            # conda runtime_env: the worker runs under the env's
            # interpreter (reference conda.py sets the context's
            # py_executable the same way); ray_tpu rides PYTHONPATH
            from ray_tpu.runtime_env.conda import worker_conda_command

            cmd, ray_env = worker_conda_command(conda_prefix, ray_env)
            env = dict(os.environ)
            env.update(ray_env)
            from ray_tpu._private.config import (
                keep_off_accelerator, whole_malloc_heaps)

            keep_off_accelerator(env)
            whole_malloc_heaps(env)
        else:
            cmd = [sys.executable, "-m", "ray_tpu._private.worker_process"]
            env = dict(os.environ)
            env.update(ray_env)
            # Workers must not grab the TPU runtime by default: work that
            # requests chips names the platform and its chips from the
            # lease's instance ids (worker_process._apply_accelerator_env)
            from ray_tpu._private.config import (
                keep_off_accelerator, whole_malloc_heaps)

            keep_off_accelerator(env)
            whole_malloc_heaps(env)
        proc = subprocess.Popen(
            cmd,
            env=env,
            stdout=out,
            stderr=err,
            start_new_session=True,
        )
        out.close()
        err.close()
        handle.proc = proc
        handle.launched_at = time.monotonic()
        handle.spawn_time = time.monotonic()
        self._pid_handles[proc.pid] = handle
        lifecycle.register_process(self.session_dir, "worker", proc.pid,
                                   self.node_id)

    def _spawn_conda_worker(self, conda_spec, env_key: Optional[str],
                            req: Dict) -> None:
        """Resolve/materialize the conda env off-loop, then spawn a worker
        under its interpreter. Env creation can take minutes (solver +
        offline package cache), so it must not block the agent's event
        loop; failures land on the lease future as a terminal
        ``runtime_env`` error (retrying would fail identically).

        One in-flight resolution per env_key: every drain pass while the
        solver runs would otherwise re-trigger a redundant create for the
        same pending lease."""
        spawning = getattr(self, "_conda_spawning", None)
        if spawning is None:
            spawning = self._conda_spawning = set()
        failed = getattr(self, "_conda_failed", None)
        if failed is None:
            failed = self._conda_failed = {}
        cached = failed.get(env_key)
        if cached is not None and \
                time.monotonic() - cached[0] < CONFIG.conda_failure_cache_s:
            # recently failed: the same spec very likely fails the same
            # way — don't re-run a minutes-long doomed solver for every
            # queued lease. The cache expires (transient solver/disk
            # failures must not poison the env for the agent's lifetime).
            fut: asyncio.Future = req["fut"]
            if not fut.done():
                fut.set_result({"error": "runtime_env",
                                "message": cached[1]})
                if req in self._pending_leases:
                    self._pending_leases.remove(req)
            return
        if env_key in spawning:
            return
        spawning.add(env_key)
        self._starting_workers += 1

        async def run() -> None:
            try:
                from ray_tpu.runtime_env.conda import ensure_conda_env

                cache_root = os.path.join(self.session_dir,
                                          "runtime_env_cache")
                os.makedirs(cache_root, exist_ok=True)
                prefix = await asyncio.get_running_loop().run_in_executor(
                    None, ensure_conda_env, conda_spec, cache_root)
            except Exception as e:
                spawning.discard(env_key)
                failed[env_key] = (time.monotonic(), str(e))
                self._starting_workers = max(0, self._starting_workers - 1)
                fut: asyncio.Future = req["fut"]
                if not fut.done():
                    fut.set_result({"error": "runtime_env",
                                    "message": str(e)})
                    if req in self._pending_leases:
                        self._pending_leases.remove(req)
                await self._drain_pending_leases()
                return
            spawning.discard(env_key)
            self._starting_workers = max(0, self._starting_workers - 1)
            self._spawn_worker(conda_prefix=prefix, env_key=env_key)
            await self._drain_pending_leases()

        spawn_tracked(run(), "agent-conda-spawn")

    async def _register_client(self, conn: Connection, p: Dict) -> Dict:
        role = p.get("role")
        conn.meta["role"] = role
        if role == "driver" and p.get("direct_addr"):
            # drivers own most objects: keep their direct addr so the
            # introspection plane (GetObjectRefs fan-out, leak watchdog)
            # can read their ref tables like any worker's
            client_id = p.get("worker_id") or f"driver-{p.get('pid', 0)}"
            conn.meta["driver_id"] = client_id
            self._driver_clients[client_id] = {
                "direct_addr": dict(p["direct_addr"]),
                "pid": p.get("pid", 0)}
        if role == "worker":
            worker_id = p["worker_id"]
            handle = self.workers.get(worker_id)
            if handle is None:
                # Worker we didn't spawn (e.g. driver-embedded, or an
                # externally-started C++ worker); track anyway.
                handle = WorkerHandle(worker_id, proc=_ForeignProc(p.get("pid", 0)))
                self.workers[worker_id] = handle
            else:
                self._starting_workers = max(0, self._starting_workers - 1)
                self._spawn_slot_freed(handle)
                self._plain_spawn_done(handle)
            # raylint: disable=R14 -- the sender is cross-language: C++
            # workers (cpp/include/ray_tpu/worker.hpp RegisterClient)
            # self-tag language:cpp via env_key; no Python send site
            # ships the key, so the linter can't see the producer
            if p.get("env_key"):
                # self-tagged env affinity (C++ workers tag themselves
                # language:cpp so only matching leases land on them)
                handle.env_key = p["env_key"]
            handle.conn = conn
            # stamp the node onto the advertised addr: lease grants carry
            # it so a same-node owner can pick the shm lane (ISSUE 11) —
            # the worker registers before it learns its own node_id
            handle.direct_addr = dict(p["direct_addr"])
            handle.direct_addr.setdefault("node_id", self.node_id)
            handle.registered.set()
            conn.meta["worker_id"] = worker_id
            if not handle.is_actor and handle.leased_to is None:
                # demand paging: a parked waiter (missed actor start)
                # beats the idle pool — the worker goes straight to work
                if not self._offer_pool_worker(handle):
                    handle.idle_since = time.monotonic()
                    self.idle_workers.append(handle)
                    await self._drain_pending_leases()
        return {
            "node_id": self.node_id,
            "head_addr": {"host": self.head_host, "port": self.head_port},
            "store_dir": self.store_dir,
            # folded-in GetNodeInfo: one fewer boot round trip per worker
            "tcp_port": self.tcp_port,
            # flight-recorder ring files live under <session>/events/
            "session_dir": self.session_dir,
            "cluster_config": CONFIG.snapshot(),
        }

    async def _on_disconnect(self, conn: Connection) -> None:
        driver_id = conn.meta.get("driver_id")
        if driver_id:
            self._driver_clients.pop(driver_id, None)
        worker_id = conn.meta.get("worker_id")
        if worker_id:
            handle = self.workers.get(worker_id)
            if handle:
                await self._handle_worker_exit(handle, "connection closed")

    async def _handle_worker_exit(self, handle: WorkerHandle, reason: str) -> None:
        pid = getattr(handle.proc, "pid", None) if handle.proc is not None \
            else None
        if pid and not handle.alive:
            lifecycle.unregister_process(self.session_dir, pid)
        if pid:
            self._pid_handles.pop(pid, None)
        popped = self.workers.pop(handle.worker_id, None)
        if popped is not None and not handle.registered.is_set():
            # died between launch and registration: the register path that
            # normally decrements the starting count never ran. Pop-guarded
            # so a handle processed by both the reaper and the actor
            # watchdog is decremented exactly once.
            self._starting_workers = max(0, self._starting_workers - 1)
        handle.exited.set()
        self._spawn_slot_freed(handle)
        self._plain_spawn_done(handle)
        if handle.actor_id and \
                self.workers_by_actor.get(handle.actor_id) is handle:
            self.workers_by_actor.pop(handle.actor_id, None)
        if handle in self.idle_workers:
            self.idle_workers.remove(handle)
        if handle.alive:
            # before its lease is released: terminate() still has to know
            # whether this process holds a chip
            handle.terminate()
        if handle.leased_to:
            self._release_lease(handle.leased_to, handle)
            # a retired chip holder frees its chip here: whoever queued
            # for it must not wait for the next cluster-view push
            await self._drain_pending_leases()
        if handle.is_actor and handle.actor_id:
            # bounded retry with jitter: ActorDied is idempotent, and
            # dropping it during a head blip would leave the actor ALIVE
            # in the registry forever (callers keep dispatching into a
            # dead worker)
            try:
                await retry_call(lambda: self.head.call(
                    "ActorDied",
                    {"actor_id": handle.actor_id, "reason": reason},
                    timeout=CONFIG.head_ping_timeout_s))
            except Exception:
                pass

    async def _worker_reaper_loop(self) -> None:
        tick = 0
        while True:
            await asyncio.sleep(CONFIG.worker_spawn_retry_s)
            tick += 1
            # Registered workers announce death through their dropped
            # agent connection (_on_disconnect) or the forkserver death
            # ledger — polling every pid each tick cost 2 syscalls per
            # live worker per 0.5s at 1,000 actors. Fast ticks scan only
            # not-yet-registered launches; a slow full sweep (every 10th
            # tick) stays as the belt-and-braces for missed events.
            full = tick % 10 == 0
            try:
                self._consume_death_ledger()
            except Exception:
                pass
            for handle in list(self.workers.values()):
                if not full and handle.registered.is_set():
                    continue
                if not handle.alive:
                    await self._handle_worker_exit(
                        handle, f"worker process exited (code {handle.proc.poll()})"
                    )
                elif (not handle.registered.is_set()
                      and handle.launched_at is not None
                      and time.monotonic() - handle.launched_at
                      > CONFIG.worker_register_timeout_s):
                    # Launched but never registered (hung before the unix
                    # socket handshake): the actor path has its own
                    # watchdog, but plain-task launches would otherwise pin
                    # their startup slot forever — after
                    # STARTUP_CONCURRENCY such hangs the admission queue is
                    # wedged node-wide. Terminate + evict + free the slot
                    # so queued spawns drain.
                    handle.terminate()
                    handle.mark_failed()
                    await self._handle_worker_exit(
                        handle, "worker failed to register before timeout")
            # Reap idle workers beyond the warm floor. The floor keeps the
            # warm pool alive; extras (burst leftovers returned from
            # leases) go after the pool idle TTL, or the long-standing
            # idle-killing cutoff, whichever expires first.
            now = time.monotonic()
            floor = max(self.max_workers, self.WARM_TARGET)
            cutoff = max(now - CONFIG.idle_worker_killing_time_ms / 1000,
                         now - float(CONFIG.worker_pool_idle_ttl_s))
            while len(self.idle_workers) > floor:
                victim = self.idle_workers[0]
                if victim.idle_since < cutoff:
                    self.idle_workers.pop(0)
                    victim.terminate()
                    self._pool_reaped += 1
                else:
                    break

    # ------------------------------------------------------------- leasing
    async def _request_worker_lease(self, conn: Connection, p: Dict) -> Dict:
        """Grant a worker lease, queue it, or reply with a spillback target.

        The hybrid policy (reference: hybrid_scheduling_policy.h:50): run
        locally while local utilization is below the spread threshold or no
        remote node is better; otherwise spill to the least-utilized feasible
        remote node.
        """
        request = ResourceSet.from_wire(p.get("resources", {}))
        pg = p.get("pg")  # [pg_id, bundle_index] or None
        if not p.get("spilled_once"):
            target = self._maybe_spillback(request, p)
            if target is not None:
                return {"spillback": target}
        fut = asyncio.get_running_loop().create_future()
        req = {"resources": request, "p": p, "fut": fut, "pg": pg}
        self._pending_leases.append(req)
        await self._drain_pending_leases()
        return await fut

    async def _request_worker_lease_batch(self, conn: Connection,
                                          p: Dict) -> Dict:
        """One frame opens N identical lease requests (ISSUE 10 batched
        RPCs). Entries resolve INDEPENDENTLY — each grant/spillback/error
        streams back as a ``LeaseItem`` push the moment it lands, so a
        fast grant is never gated on a sibling queued behind capacity;
        the frame's reply just closes the batch (same shape as the worker
        PushTaskBatchStream protocol)."""
        n = max(1, int(p.get("n", 1)))
        bid = p.get("b")
        _note_hist(self._lease_batch_hist, n)

        async def one(i: int) -> None:
            try:
                reply = await self._request_worker_lease(conn, p)
            except Exception as e:  # noqa: BLE001 — per-entry blast radius
                reply = {"error": "lease", "message": repr(e)}
            try:
                conn.push_nowait("LeaseItem", {"b": bid, "i": i, "r": reply})
            except Exception:
                pass  # requester gone; the closing reply fails too

        await asyncio.gather(*[one(i) for i in range(n)])
        return {"n": n}

    # ----------------------------------------- batched readiness relay
    async def _report_actor_ready(self, conn: Connection, p: Dict) -> bool:
        """Worker→head ActorReady relay (ISSUE 10): workers report over
        their (unix) agent connection; the agent coalesces a creation
        burst into ONE ActorReadyBatch head RPC (+ one WAL group commit
        head-side) per flush window. The worker is acked only after the
        head acked — its retry/exit-on-persistent-failure contract (a
        worker the head never acked exits and is no zombie) is preserved
        end to end."""
        fut = asyncio.get_running_loop().create_future()
        self._ready_queue.append((p, fut))
        if not self._ready_flush_armed:
            self._ready_flush_armed = True
            asyncio.get_running_loop().call_later(
                max(CONFIG.actor_ready_batch_window_ms, 0) / 1000.0,
                lambda: spawn_tracked(self._flush_ready_batch(),
                                      "agent-ready-flush"))
        return await fut

    async def _flush_ready_batch(self) -> None:
        self._ready_flush_armed = False
        batch = self._ready_queue
        self._ready_queue = []
        if not batch:
            return
        _note_hist(self._ready_batch_hist, len(batch))
        items = [p for p, _f in batch]
        try:
            await retry_call(lambda: self.head.call(
                "ActorReadyBatch",
                {"items": items, "node_id": self.node_id},
                timeout=CONFIG.control_rpc_timeout_s))
        except Exception as e:
            for _p, fut in batch:
                if not fut.done():
                    fut.set_exception(
                        ConnectionError(f"ActorReadyBatch failed: {e!r}"))
            return
        for _p, fut in batch:
            if not fut.done():
                fut.set_result(True)

    async def _get_worker_pool_stats(self, conn: Connection, p) -> Dict:
        return {
            "warm_target": self.WARM_TARGET,
            "warm": self._warm_idle_count(),
            "idle": len(self.idle_workers),
            "workers": len(self.workers),
            "starting": self._starting_workers,
            "spawning_plain": self._spawning_plain,
            "hits": self._pool_hits,
            "misses": self._pool_misses,
            # demand-paged handouts (ISSUE 11): missed-then-served by a
            # pre-forked pool worker instead of a dedicated cold fork
            "demand_hits": self._demand_hits,
            "waiters": sum(1 for f in self._pool_waiters
                           if not f.done()),
            "recent_demand": self._recent_demand(),
            "refills": self._pool_refills,
            "reaped": self._pool_reaped,
            "spawned_total": getattr(self, "_workers_spawned", 0),
            "lease_batch_hist": dict(self._lease_batch_hist),
            "ready_batch_hist": dict(self._ready_batch_hist),
        }

    def _maybe_spillback(self, request: ResourceSet, p: Dict) -> Optional[Dict]:
        target = self._maybe_spillback_inner(request, p)
        if target is not None:
            # feeds ray_tpu_scheduler_spillbacks_total
            self._spillback_count = getattr(self, "_spillback_count", 0) + 1
        return target

    def _maybe_spillback_inner(self, request: ResourceSet,
                               p: Dict) -> Optional[Dict]:
        strategy = p.get("scheduling_strategy") or {}
        if isinstance(strategy, dict) and strategy.get("type") == "node_label":
            hard = strategy.get("hard") or {}
            soft = strategy.get("soft") or {}
            local_ok = (label_constraints_match(self.resources.labels, hard)
                        and request.feasible_on(self.resources.total))
            # Candidate remotes that satisfy hard + feasibility; prefer
            # soft-matching ones (best-effort, reference: node-label soft).
            candidates = []
            for node_id, view in self.cluster_view.items():
                if node_id == self.node_id or not view.get("alive", True):
                    continue
                nr = NodeResources.from_wire(view["resources"])
                if (label_constraints_match(nr.labels, hard)
                        and request.feasible_on(nr.total)):
                    candidates.append(
                        (label_constraints_match(nr.labels, soft),
                         node_id, view["addr"]))
            if local_ok and (label_constraints_match(self.resources.labels, soft)
                             or not any(c[0] for c in candidates)):
                return None
            for prefer_soft in (True, False):
                for soft_ok, node_id, addr in candidates:
                    if soft_ok == prefer_soft:
                        return {"node_id": node_id, "addr": addr}
            return None
        if isinstance(strategy, dict) and strategy.get("type") == "node_affinity":
            target_node = strategy.get("node_id")
            if target_node and target_node != self.node_id:
                view = self.cluster_view.get(target_node)
                if view:
                    return {"node_id": target_node, "addr": view["addr"]}
            return None
        if p.get("pg"):
            return None  # PG leases run where the bundle lives; caller targeted us
        spread = isinstance(strategy, dict) and strategy.get("type") == "spread"
        local_feasible = request.feasible_on(self.resources.total)
        local_fits = request.fits(self.resources.available)
        local_util = self.resources.utilization()
        if (
            local_feasible
            and local_fits
            and not spread
            and local_util < CONFIG.scheduler_spread_threshold
        ):
            return None
        # Consider remote nodes from the gossip view.
        best = None
        best_util = None
        for node_id, view in self.cluster_view.items():
            if node_id == self.node_id or not view.get("alive", True):
                continue
            nr = NodeResources.from_wire(view["resources"])
            self._apply_recent_spills(node_id, nr)
            if not request.feasible_on(nr.total):
                continue
            if not request.fits(nr.available):
                continue
            util = nr.utilization()
            if best is None or util < best_util:
                best, best_util = (node_id, view["addr"]), util
        if best is None:
            return None
        if not local_feasible or not local_fits:
            self._record_spill(best[0], request)
            return {"node_id": best[0], "addr": best[1]}
        if spread or local_util >= CONFIG.scheduler_spread_threshold:
            if best_util < local_util:
                self._record_spill(best[0], request)
                return {"node_id": best[0], "addr": best[1]}
        return None

    @property
    def SPILL_LEDGER_TTL_S(self) -> float:
        return CONFIG.spill_ledger_ttl_ms / 1000.0

    def _apply_recent_spills(self, node_id: str, nr: NodeResources) -> None:
        ledger = self._recent_spills.get(node_id)
        if not ledger:
            return
        now = time.monotonic()
        live = [(t, rs) for t, rs in ledger if t > now]
        if live:
            self._recent_spills[node_id] = live
        else:
            self._recent_spills.pop(node_id, None)
        for _t, rs in live:
            nr.available.subtract(rs, allow_negative=True)

    def _record_spill(self, node_id: str, request: ResourceSet) -> None:
        if os.environ.get("RAY_TPU_DEBUG"):
            print(f"SPILL {self.node_id[:8]} -> {node_id[:8]} "
                  f"{request.to_dict()}", file=sys.stderr, flush=True)
        self._recent_spills.setdefault(node_id, []).append(
            (time.monotonic() + self.SPILL_LEDGER_TTL_S, request))

    async def _drain_pending_leases(self) -> None:
        made_progress = True
        while made_progress and self._pending_leases:
            made_progress = False
            for req in list(self._pending_leases):
                if await self._try_grant(req):
                    self._pending_leases.remove(req)
                    made_progress = True
                    continue
                # A queued request that this node can never (or not soon)
                # satisfy gets re-evaluated for spillback as the gossip view
                # evolves — otherwise a request that arrived before the view
                # caught up would wedge here forever.
                p = req["p"]
                if not p.get("spilled_once"):
                    target = self._maybe_spillback(req["resources"], p)
                    if target is not None and not req["fut"].done():
                        req["fut"].set_result({"spillback": target})
                        self._pending_leases.remove(req)
                        made_progress = True

    async def _try_grant(self, req: Dict) -> bool:
        request: ResourceSet = req["resources"]
        strategy = req["p"].get("scheduling_strategy") or {}
        if isinstance(strategy, dict) and strategy.get("type") == "node_label":
            if not label_constraints_match(self.resources.labels,
                                           strategy.get("hard") or {}):
                return False
        pg = req.get("pg")
        pg_key = None
        if pg:
            pg_key = self._match_pg_bundle(pg, request)
            if pg_key is None:
                if any(k[0] == pg[0] for k in self._pg_bundles):
                    return False  # bundles exist but are full: stay queued
                # Every bundle of this group is gone from this node — the
                # group was removed; fail the lease instead of wedging it.
                fut: asyncio.Future = req["fut"]
                if not fut.done():
                    fut.set_result({"error": "pg_removed"})
                return True
        elif not request.fits(self.resources.available):
            return False
        env_key = req["p"].get("env_key")
        container = req["p"].get("container")
        conda = req["p"].get("conda")
        # container/conda envs apply at SPAWN (the process must start
        # inside the image / under the env's interpreter), so a pristine
        # host worker can never serve them: match only workers already
        # tagged with this env_key
        spawn_env = bool(container or conda)
        # language-tagged leases ({"language": "cpp"}) can only run on a
        # worker of that language; those register EXTERNALLY (reference:
        # C++ worker processes joining the cluster) — never spawn a
        # Python worker for them, just wait for one to appear
        lang_env = _env_key_language(env_key) is not None
        # One process per chip: jax binds a process to its platform and
        # chips at first use and holds them until it exits. A chip lease
        # goes to a worker that has run nothing, and that worker ends with
        # the lease (_return_worker).
        worker = self._pop_idle_worker(
            env_key, tagged_only=spawn_env or lang_env,
            unused_only=request.get(TPU) >= 1)
        if worker is None:
            if lang_env:
                return False
            if len(self.workers) + self._starting_workers < self.max_workers + 8 \
                    or self._evict_mismatched_idle():
                if conda and not container:
                    self._spawn_conda_worker(conda, env_key, req)
                else:
                    self._spawn_worker(container=container,
                                       env_key=env_key if spawn_env else None)
            return False
        # allocate resources
        if pg:
            assigned_instances = self._bundle_take(pg_key, request)
        else:
            assigned_instances = self.resources.allocate(request, owner=worker.worker_id) or {}
            self._resources_dirty = True
        worker.chips = assigned_instances
        worker.used = True
        self._lease_counter += 1
        lease_id = f"{self.node_id[:8]}-{self._lease_counter}"
        worker.leased_to = lease_id
        worker.assigned_resources = request
        worker.lease_owner = req["p"].get("owner", "")
        worker.lease_start = time.monotonic()
        worker.lease_retriable = bool(req["p"].get("retriable", True))
        self.leases[lease_id] = worker
        worker.meta_pg = list(pg_key) if pg_key else None
        fut: asyncio.Future = req["fut"]
        if not fut.done():
            if env_key is not None:
                # tag only on a delivered grant: the worker will apply this
                # runtime_env on its first task and can never serve another
                worker.env_key = env_key
            fut.set_result(
                {
                    "grant": {
                        "lease_id": lease_id,
                        "worker_id": worker.worker_id,
                        "addr": worker.direct_addr,
                        "node_id": self.node_id,
                        "assigned_instances": assigned_instances,
                    }
                }
            )
        else:
            self._release_lease(lease_id, worker)
            self.idle_workers.append(worker)
        return True

    def _pop_idle_worker(self, env_key: Optional[str] = None,
                         tagged_only: bool = False,
                         unused_only: bool = False
                         ) -> Optional[WorkerHandle]:
        # prune dead workers (incl. pid-ledger deaths and connections
        # already mid-close — the disconnect callback may not have run
        # yet), then prefer an env-matching worker, falling back to a
        # pristine one (tagged by the caller on grant).
        # tagged_only: spawn-time envs (container) can never ride a
        # pristine host worker — exact tag match or nothing.
        # unused_only: only a worker that has run nothing (a chip lease
        # must reach jax before anything else has).
        self.idle_workers = [w for w in self.idle_workers
                             if w.alive and w.registered.is_set()
                             and (w.conn is None or not w.conn.closed)]
        tiers = (env_key,) if tagged_only else (env_key, None)
        for tier in tiers:
            for i in range(len(self.idle_workers) - 1, -1, -1):
                w = self.idle_workers[i]
                if w.env_key == tier and not (unused_only and w.used):
                    return self.idle_workers.pop(i)
        return None

    def _evict_mismatched_idle(self) -> bool:
        """Kill one idle worker with a foreign runtime_env to make room for
        a fresh process (its env cannot be un-applied)."""
        for i, w in enumerate(self.idle_workers):
            # externally-managed language workers (C++) are not ours to
            # recycle for Python leases
            if w.env_key is not None and \
                    _env_key_language(w.env_key) is None:
                self.idle_workers.pop(i)
                w.terminate()
                self.workers.pop(w.worker_id, None)
                self._env_evictions = getattr(self, "_env_evictions", 0) + 1
                return True
        return False

    async def _return_worker(self, conn: Connection, p: Dict) -> bool:
        lease_id = p["lease_id"]
        worker = self.leases.get(lease_id)
        if worker is None:
            return False
        if worker.chips.get(TPU) and worker.alive:
            # the process holds its chips until it is gone: the lease (and
            # with it the chips) is released from _handle_worker_exit
            worker.terminate()
            return True
        self._release_lease(lease_id, worker)
        if p.get("worker_exiting") or not worker.alive:
            return True
        if self._offer_pool_worker(worker):
            return True  # returned lease feeds a parked actor start
        worker.idle_since = time.monotonic()
        self.idle_workers.append(worker)
        await self._drain_pending_leases()
        return True

    def _release_lease(self, lease_id: str, worker: WorkerHandle) -> None:
        self.leases.pop(lease_id, None)
        if worker.assigned_resources is not None:
            pg = getattr(worker, "meta_pg", None)
            if pg:
                self._bundle_give_back((pg[0], pg[1]),
                                       worker.assigned_resources,
                                       worker.chips)
            else:
                self.resources.release(worker.assigned_resources, owner=worker.worker_id)
                self._resources_dirty = True
        worker.assigned_resources = None
        worker.chips = {}
        worker.leased_to = None
        worker.meta_pg = None

    # ---------------------------------------------------------------- actors
    async def _start_actor(self, p: Dict) -> None:
        rec_ev = _events.REC
        ev_trace = rec_ev.new_trace() if rec_ev.enabled and rec_ev.sample() \
            else None
        ev_t0 = time.time() if ev_trace is not None else 0.0
        spec = p["spec"]
        request = ResourceSet.from_wire(spec.get("resources", {}))
        pg = spec.get("pg")
        if pg:
            # Wait for bundle capacity like the non-PG path waits for node
            # resources: a just-returned lease may still hold the bundle.
            deadline = time.monotonic() + CONFIG.actor_creation_timeout_ms / 1000
            while True:
                key = self._match_pg_bundle(pg, request)
                if key is not None:
                    break
                if not any(k[0] == pg[0] for k in self._pg_bundles) or \
                        time.monotonic() > deadline:
                    await self.head.call(
                        "ActorDied",
                        {"actor_id": p["actor_id"],
                         "reason": "pg bundle unavailable"},
                        timeout=CONFIG.control_rpc_timeout_s,
                    )
                    return
                await asyncio.sleep(CONFIG.actor_resource_wait_poll_s)
            pg = list(key)
            assigned = self._bundle_take(key, request)
        else:
            deadline = time.monotonic() + CONFIG.actor_creation_timeout_ms / 1000
            while not request.fits(self.resources.available):
                if time.monotonic() > deadline:
                    await self.head.call(
                        "ActorDied",
                        {"actor_id": p["actor_id"],
                         "reason": "timed out waiting for actor resources"},
                        timeout=CONFIG.control_rpc_timeout_s,
                    )
                    return
                await asyncio.sleep(CONFIG.actor_resource_wait_poll_s)
            assigned = self.resources.allocate(request, owner=p["actor_id"]) or {}
            self._resources_dirty = True
        # Warm-pool lease (ISSUE 10): a pre-booted pristine worker skips
        # the whole fork + loop setup + handshake + store-attach boot
        # (~0.1 core-s measured on a CPU box, round 5) — actor creation
        # pays only class unpickle + __init__. Cold fork is the fallback,
        # never a failure mode.
        wants_chip = bool(assigned.get(TPU))
        handle = self._lease_warm_worker(unused_only=wants_chip)
        ev_source = "warm_hit"
        if handle is not None:
            self._pool_hits += 1
        else:
            # a chip actor never parks for the pool: a returned lease's
            # worker may be offered there, and it has already run code
            if self.warm_lease_enabled and not wants_chip:
                # demand paging (ISSUE 11): park for the next pool
                # registration — the pre-forked pipeline from
                # _note_actor_demand is already booting toward us
                handle = await self._wait_pool_worker()
            if handle is not None:
                self._demand_hits += 1
                ev_source = "demand_hit"
            else:
                self._pool_misses += 1
                handle = self._spawn_worker()
                ev_source = "fork"
        if ev_trace is not None:
            # resource wait + pool decision, tagged with how the start was
            # served — the per-hop answer to "warm hit or cold fork?"
            rec_ev.record("actor_start::" + ev_source, "actor", ev_t0,
                          time.time() - ev_t0, ev_trace[0], ev_trace[1], 0,
                          {"actor": str(p.get("actor_id", ""))[:16]})
        handle.is_actor = True
        handle.actor_id = p["actor_id"]
        handle.chips = assigned
        handle.used = True
        handle.assigned_resources = None  # released via actor-death path below
        self.workers_by_actor[p["actor_id"]] = handle

        async def finish():
            # the register timeout counts from the actual LAUNCH (fork),
            # not from enqueue: under spawn admission a 1000-actor burst
            # legitimately queues for minutes
            while True:
                try:
                    await asyncio.wait_for(handle.registered.wait(), 5.0)
                    break
                except asyncio.TimeoutError:
                    if handle.worker_id not in self.workers or (
                            handle.launched_at is not None
                            and time.monotonic() - handle.launched_at
                            > CONFIG.worker_register_timeout_s):
                        # a hung launch must not pin its startup slot or
                        # linger in the pool — terminate + evict, or the
                        # admission queue wedges node-wide after
                        # STARTUP_CONCURRENCY such hangs
                        handle.terminate()
                        handle.mark_failed()
                        if self.workers.pop(handle.worker_id, None) \
                                is not None and \
                                not handle.registered.is_set():
                            # same accounting as _handle_worker_exit: the
                            # register path that decrements never ran
                            self._starting_workers = max(
                                0, self._starting_workers - 1)
                        handle.exited.set()
                        self._spawn_slot_freed(handle)
                        await self.head.call(
                            "ActorDied",
                            {"actor_id": p["actor_id"],
                             "reason": "worker failed to start"},
                            timeout=CONFIG.control_rpc_timeout_s,
                        )
                        return
            await handle.conn.push(
                "BecomeActor",
                {"spec": spec, "actor_id": p["actor_id"],
                 "assigned_instances": assigned,
                 "actor_start": ev_source},
            )

        spawn_tracked(finish(), "agent-actor-finish")

        # Hold the resources until the actor dies. An evicted/never-
        # launched handle (no longer in the pool) counts as dead — its
        # resources must flow back (the spawn may have failed with
        # proc=None, which `alive` alone reads as still-starting).
        async def watch_release():
            # event-driven with a slow fallback poll: N live actors must
            # not cost the loop N wakeups per poll period
            while handle.alive and handle.worker_id in self.workers:
                try:
                    await asyncio.wait_for(
                        handle.exited.wait(),
                        timeout=CONFIG.actor_liveness_poll_s)
                except asyncio.TimeoutError:
                    pass
            if pg:
                self._bundle_give_back((pg[0], pg[1]), request, assigned)
            else:
                self.resources.release(request, owner=p["actor_id"])
                self._resources_dirty = True

        spawn_tracked(watch_release(), "agent-actor-release")

    def _kill_actor_worker(self, actor_id: str) -> None:
        handle = self.workers_by_actor.get(actor_id)
        if handle is None:
            return
        try:
            handle.terminate()
        except Exception:
            pass

        # SIGTERM is advisory: a worker wedged inside a native collective
        # (dead-peer jax/gloo rendezvous holds the GIL in C++) never runs
        # the Python signal handler and only dies at the collective's own
        # timeout (~100s) — which stalls the killed actor's PG bundle and
        # wedges the elastic restart behind it. Escalate to SIGKILL after
        # a bounded grace.
        async def escalate():
            try:
                await asyncio.wait_for(
                    handle.exited.wait(),
                    timeout=float(CONFIG.worker_kill_escalation_s))
            except asyncio.TimeoutError:
                if handle.alive:
                    handle.hard_kill()

        spawn_tracked(escalate(), "agent-kill-escalate")

    # ------------------------------------------------------ placement groups
    def _match_pg_bundle(self, pg, request: ResourceSet):
        """Map a lease/actor pg target onto a concrete local bundle.

        bundle_index -1 means "any bundle of the group" (reference semantics:
        placement_group.py bundle_index default); scan this node's bundles of
        the group for one the request fits.
        """
        pg_id, idx = pg[0], pg[1]
        if idx is not None and idx >= 0:
            pool = self._pg_available.get((pg_id, idx))
            if pool is not None and request.fits(pool):
                return (pg_id, idx)
            if (pg_id, idx) in self._pg_bundles:
                return None  # exists but full — caller decides to queue
            return None
        for key, pool in sorted(self._pg_available.items()):
            if key[0] == pg_id and request.fits(pool):
                return key
        return None

    def _prepare_pg_bundle(self, p: Dict) -> bool:
        key = (p["pg_id"], p["bundle_index"])
        if key in self._pg_bundles:
            return True
        request = ResourceSet.from_wire(p["resources"])
        chips = self.resources.allocate(request)
        if chips is None:
            return False
        self._pg_bundles[key] = request
        self._pg_available[key] = request.copy()
        self._pg_chips[key] = chips
        self._resources_dirty = True
        return True

    def _bundle_take(self, key, request: ResourceSet) -> Dict[str, list]:
        """Place work in a bundle: its share leaves the bundle's pool, and
        with it the instance ids the bundle reserved for that share."""
        self._pg_available[key].subtract(request)
        free = self._pg_chips[key]
        taken: Dict[str, list] = {}
        for name in list(free):
            n = int(request.get(name))
            if n >= 1:
                taken[name], free[name] = free[name][:n], free[name][n:]
        return taken

    def _bundle_give_back(self, key, request: ResourceSet,
                          chips: Dict[str, list]) -> None:
        pool = self._pg_available.get(key)
        if pool is None:
            # the group was returned under its tenant: what the tenant
            # held goes straight back to the node
            self.resources.release(request, instances=chips)
            self._resources_dirty = True
            return
        pool.add(request)
        free = self._pg_chips[key]
        for name, ids in chips.items():
            free[name] = sorted(free.get(name, []) + ids)

    def _return_pg_bundle(self, p: Dict) -> None:
        key = (p["pg_id"], p["bundle_index"])
        if self._pg_bundles.pop(key, None) is not None:
            # Only what no tenant holds goes back now. A process keeps its
            # chip until it is dead, so a tenant's share (and its instance
            # ids) returns when it leaves (_bundle_give_back).
            self.resources.release(self._pg_available.pop(key),
                                   instances=self._pg_chips.pop(key))
            self._resources_dirty = True
        # Queued leases targeting this group must fail now, not hang: the
        # drain's _try_grant sees the bundles are gone and replies pg_removed.
        spawn_tracked(self._drain_pending_leases(), "agent-pg-drain")

    # --------------------------------------------------------- object plane
    async def _object_sealed(self, conn: Connection, p: Dict) -> None:
        hex_id = p["object_id"]
        self.store.on_sealed(hex_id, p["size"])
        if "replayable" in p:
            # lineage hints (ISSUE 17): drive the store's lineage-aware
            # eviction (prefer dropping cheap-to-replay copies)
            self.store.note_lineage(hex_id, bool(p.get("replayable")),
                                    float(p.get("exec_ms") or 0.0))
        if p.get("zero_copy"):
            self._zero_copy_puts += 1
        owner = p.get("owner")
        if owner:
            # object ledger (ISSUE 15): remember who OWNS each sealed
            # object (+ its creating task/callsite) so the leak watchdog
            # can interrogate the owner later and attribution survives
            # the owner row dropping (free in flight). Pruned on free
            # and by the watchdog/stats scan when the object leaves the
            # store.
            self._object_owners[hex_id] = {
                "owner": owner, "task": p.get("task") or "",
                "callsite": p.get("callsite") or "",
                "sealed_at": time.time()}
        for fut in self._object_waits.pop(hex_id, []):
            if not fut.done():
                fut.set_result(True)

    async def _wait_objects(self, conn: Connection, p: Dict) -> Dict:
        """Wait until num_returns of the ids are local, pulling remotes.

        p: {ids: [hex], owners: {hex: owner_addr}, locations: {hex:
        [addr]}, num_returns, timeout_ms}. ``locations`` are the
        caller's last-known holders (owner directory / borrow reply) —
        used as a routed-fetch fallback when the owner is unreachable.
        """
        ids: List[str] = p["ids"]
        owners: Dict[str, Dict] = p.get("owners", {})
        hints: Dict[str, List[Dict]] = p.get("locations", {}) or {}
        num_returns = p.get("num_returns", len(ids))
        timeout_ms = p.get("timeout_ms")
        tc = p.get("tc")  # caller's trace context (sampled get)
        futs = {}
        for hex_id in ids:
            waited_owner = owners.get(hex_id)
            if waited_owner and hex_id not in self._object_owners:
                # pulls announce owners too: a pulled copy on this node is
                # leak-scannable even though it was sealed elsewhere
                self._object_owners[hex_id] = {
                    "owner": waited_owner, "task": "",
                    "sealed_at": time.time()}
            if self.store.contains(hex_id):
                continue
            fut = asyncio.get_running_loop().create_future()
            self._object_waits.setdefault(hex_id, []).append(fut)
            # re-attaching invalidates any pending orphan-reap timer
            self._pull_orphan_stamp.pop(hex_id, None)
            futs[hex_id] = fut
            owner = owners.get(hex_id)
            if owner and hex_id not in self._pulls_inflight:
                self._pulls_inflight[hex_id] = asyncio.get_running_loop().create_task(
                    self._pull_object(hex_id, owner, tc=tc,
                                      hint_locs=hints.get(hex_id))
                )

        def ready_count() -> int:
            return sum(1 for h in ids if self.store.contains(h))

        deadline = None if timeout_ms is None else time.monotonic() + timeout_ms / 1000
        try:
            while ready_count() < num_returns:
                pending = [f for f in futs.values() if not f.done()]
                if not pending:
                    break
                wait_timeout = None
                if deadline is not None:
                    wait_timeout = deadline - time.monotonic()
                    if wait_timeout <= 0:
                        break
                # Cap each wait to re-poll the (filesystem-authoritative) store:
                # seal notifications are fire-and-forget and can be lost if the
                # sealing worker dies right after store.seal — the object is
                # still on disk, so the poll keeps waiters from hanging forever.
                poll_s = CONFIG.object_wait_poll_ms / 1000.0
                poll = poll_s if wait_timeout is None \
                    else min(wait_timeout, poll_s)
                done, _ = await asyncio.wait(
                    pending, timeout=poll, return_when=asyncio.FIRST_COMPLETED
                )
                if not done and deadline is not None \
                        and time.monotonic() >= deadline:
                    break
        finally:
            # Deregister this call's waiters; when an object's LAST waiter
            # leaves (get timed out, caller gone), cancel its in-flight
            # pull instead of letting it burn the full pull deadline
            # re-locating an object nobody wants.
            for hex_id, fut in futs.items():
                waiters = self._object_waits.get(hex_id)
                if waiters is None:
                    continue
                try:
                    waiters.remove(fut)
                except ValueError:
                    pass
                if not waiters:
                    del self._object_waits[hex_id]
                    self._cancel_orphan_pull(hex_id)
        ready = [h for h in ids if self.store.contains(h)]
        not_ready = [h for h in ids if h not in set(ready)]
        return {"ready": ready, "not_ready": not_ready}

    def _cancel_orphan_pull(self, hex_id: str) -> None:
        """Schedule cancellation of the pull task for an object with no
        waiters left — after a grace window, so a get() retried on a short
        timeout re-attaches to the running transfer instead of restarting
        it from byte 0. If the grace expires with still no waiter, the
        task is popped + cancelled (eagerly popped so a later waiter
        starts fresh instead of parking behind a zombie) and parks in
        ``_pulls_draining`` so that fresh pull defers to its cleanup (the
        old abort would unlink the new transfer's unsealed allocation)."""
        task = self._pulls_inflight.get(hex_id)
        if task is None or task.done():
            return
        stamp = time.monotonic()
        self._pull_orphan_stamp[hex_id] = stamp

        async def reap():
            await asyncio.sleep(CONFIG.object_pull_orphan_grace_s)
            if self._pull_orphan_stamp.get(hex_id) != stamp:
                # a waiter re-attached (stamp popped) or a LATER detach
                # re-stamped — only the newest timer may cancel, so the
                # grace always runs full length from the last departure
                return
            self._pull_orphan_stamp.pop(hex_id, None)
            if self._object_waits.get(hex_id):
                return  # a new waiter re-attached; keep the pull
            if self._pulls_inflight.get(hex_id) is not task or task.done():
                return  # finished, or a different pull took the slot
            self._pulls_inflight.pop(hex_id, None)
            task.cancel()
            self._pulls_draining.setdefault(hex_id, []).append(task)

            def _drained(t, h=hex_id):
                lst = self._pulls_draining.get(h)
                if lst is not None:
                    try:
                        lst.remove(t)
                    except ValueError:
                        pass
                    if not lst:
                        self._pulls_draining.pop(h, None)

            task.add_done_callback(_drained)

        spawn_tracked(reap(), "agent-orphan-pull-reap")

    async def _pull_object(self, hex_id: str, owner: Dict,
                           tc=None, hint_locs=None) -> None:
        """Flight-recorder shell around the pull: one ``pull`` span per
        admission, stitched under the caller's get() trace when the
        WaitObjects frame carried one, else its own sampled root."""
        rec = _events.REC
        if rec.enabled and (tc is not None or rec.sample()):
            if tc is None:
                trace, parent = rec.new_trace()[0], 0
            else:
                trace, parent = tc[0], tc[1]
            span = rec.next_id()
            t0 = time.time()
            rec.open_marker("pull", "object", trace, span, parent,
                            {"obj": hex_id[:16]})
            try:
                await self._pull_object_inner(hex_id, owner,
                                              tc=(trace, span),
                                              hint_locs=hint_locs)
            finally:
                rec.record("pull", "object", t0, time.time() - t0,
                           trace, span, parent,
                           {"obj": hex_id[:16],
                            "sealed": bool(self.store.contains(hex_id))})
        else:
            await self._pull_object_inner(hex_id, owner,
                                          hint_locs=hint_locs)

    async def _pull_object_inner(self, hex_id: str, owner: Dict,
                                 tc=None, hint_locs=None) -> None:
        """Owner-directed pull (reference: pull_manager.h + ownership-based
        object directory): ask the owner where the object lives, then hand
        the holder set to the pull manager — windowed pipeline, multi-
        holder striping, budgeted admission (pull_manager.py) — or take
        the inline value from the owner."""
        task = asyncio.current_task()
        try:
            while True:
                # cancelled predecessors may still be tearing down their
                # transfers (aborting the unsealed store allocation we
                # would otherwise collide with). asyncio.wait — NOT gather
                # — so cancelling THIS pull mid-wait never re-cancels a
                # predecessor out of its cleanup.
                draining = [t for t in self._pulls_draining.get(hex_id, [])
                            if not t.done()]
                if not draining:
                    break
                await asyncio.wait(draining)
            deadline = time.monotonic() + CONFIG.object_pull_deadline_s
            dead_rounds = 0
            while time.monotonic() < deadline:
                if self.store.contains(hex_id):
                    return
                try:
                    client = await self.pool.get(owner["host"], owner["port"])
                    loc = await client.call(
                        "LocateObject", {"object_id": hex_id},
                        timeout=CONFIG.object_locate_timeout_s
                    )
                except asyncio.CancelledError:
                    raise
                except Exception:
                    # Owner unreachable: fall back to the caller's hinted
                    # holders (borrow-reply locations survive the owner)
                    # before the blind sleep-retry — a borrower can often
                    # restore from a live replica while the owner's node
                    # is mid-recovery.
                    hinted = [
                        a for a in (hint_locs or [])
                        if not (a.get("host") == "127.0.0.1"
                                and a.get("port") == self.tcp_port)]
                    if hinted:
                        st = await self._fetch_routed(hex_id, hinted,
                                                      tc=tc)
                        if st == "ok":
                            self._notify_sealed(hex_id)
                            return
                    await asyncio.sleep(CONFIG.object_pull_retry_s)
                    continue
                if loc is None:
                    await asyncio.sleep(CONFIG.object_unlocated_retry_s)
                    continue
                if loc.get("inline") is not None:
                    data = loc["inline"]
                    self.store.client.put_bytes(ObjectID.from_hex(hex_id), data)
                    self.store.on_sealed(hex_id, len(data))
                    self._notify_sealed(hex_id)
                    return
                remote_locs = [
                    a for a in loc.get("locations", [])
                    if not (a.get("host") == "127.0.0.1"
                            and a.get("port") == self.tcp_port)]
                if not remote_locs:
                    # remote-tier spill: this node dropped its local copy
                    # against recorded remote holders — those are a valid
                    # restore source even when the owner only lists us
                    remote_locs = self.store.remote_sources_for(hex_id)
                st = "absent"
                if remote_locs:
                    st = await self._fetch_routed(hex_id, remote_locs,
                                                  tc=tc)
                if st == "ok":
                    self._notify_sealed(hex_id)
                    # Tell the owner we now hold a copy.
                    try:
                        await client.push(
                            "ObjectLocationAdded",
                            {"object_id": hex_id,
                             "addr": {"host": "127.0.0.1", "port": self.tcp_port}},
                        )
                    except Exception:
                        pass
                    return
                if remote_locs and st == "conn":
                    # Every advertised holder is connection-dead (not merely
                    # missing the object or a local hiccup). After a few
                    # rounds, fail the wait so the owner's lineage recovery
                    # can resubmit the creating task instead of burning the
                    # caller's whole get deadline (reference: pull_manager
                    # hands off to reconstruction on location death).
                    dead_rounds += 1
                    if dead_rounds >= CONFIG.pull_dead_holder_rounds:
                        for fut in self._object_waits.pop(hex_id, []):
                            if not fut.done():
                                fut.set_result(False)
                        return
                else:
                    dead_rounds = 0
                await asyncio.sleep(CONFIG.object_pull_round_s)
            # deadline exhausted: fail the waiters so a timeout-less
            # WaitObjects (and the get() blocked on it) sees a lost
            # verdict instead of polling forever on futures nobody will
            # ever resolve
            for fut in self._object_waits.pop(hex_id, []):
                if not fut.done():
                    fut.set_result(False)
        finally:
            # identity-guarded: an orphan-cancel may have popped this task
            # already and a NEW pull registered under the same object
            if self._pulls_inflight.get(hex_id) is task:
                self._pulls_inflight.pop(hex_id, None)

    def _notify_sealed(self, hex_id: str) -> None:
        for fut in self._object_waits.pop(hex_id, []):
            if not fut.done():
                fut.set_result(True)

    async def _fetch_routed(self, hex_id: str, holders: List[Dict],
                            tc=None) -> str:
        """Route one pull: the spanning broadcast tree for large objects
        (K consumers of the same object share O(log N) distribution via
        chunk-level relay) with transparent degradation to the plain
        multi-holder striped pull — the tree is an optimization layer,
        never a new failure mode."""
        from ray_tpu._private import broadcast

        rec = _events.REC

        async def spanned(name, coro, n_holders):
            if tc is None or not rec.enabled:
                return await coro
            t0 = time.time()
            st = await coro
            rec.record(name, "object", t0, time.time() - t0, tc[0],
                       rec.next_id(), tc[1],
                       {"obj": hex_id[:16], "st": st,
                        "holders": n_holders})
            return st

        size, alive, any_absent = await self.pulls._probe_meta(
            hex_id, holders)
        if size is None:
            return "absent" if any_absent else "conn"
        meta = (size, alive, any_absent)
        if size < CONFIG.bcast_min_bytes:
            return await spanned(
                "stripe_pull", self.pulls.fetch(hex_id, alive, meta=meta),
                len(alive))
        progress = self.pulls.register_progress(hex_id, size)
        try:
            st = await spanned(
                "bcast_pull",
                broadcast.bcast_fetch(self, hex_id, size, alive, progress),
                len(alive))
            if st == "fallback":
                # keep the SAME progress registered: children this node
                # was assigned relay off the striped pull just the same
                st = await spanned(
                    "stripe_pull",
                    self.pulls.fetch(hex_id, alive, meta=meta,
                                     progress=progress),
                    len(alive))
            return st
        finally:
            self.pulls.unregister_progress(hex_id, progress)

    async def _fetch_object_meta(self, conn: Connection, p: Dict) -> Dict:
        hex_id = p["object_id"]
        view = self.store.read_maybe_spilled(hex_id)
        if view is not None:
            return {"exists": True, "size": len(view)}
        # mid-pull relay source: a broadcast child probing its assigned
        # parent must see the advertised size, not an absent verdict
        prog = self.pulls.active.get(hex_id)
        if prog is not None and not prog.failed:
            return {"exists": True, "size": prog.size, "partial": True}
        return {"exists": False}

    async def _fetch_object_chunk(self, conn: Connection, p: Dict):
        hex_id = p["object_id"]
        # Per-transfer view cache: a windowed pull asks for the SAME object
        # dozens of times in a burst; re-resolving the store view per chunk
        # (native store: lock + pin + finalizer each) was measurable on the
        # serve hot path. Tiny LRU, and TIME-BOUNDED: a cached view pins
        # its object (native arena LRU cannot evict it), so entries idle
        # past the TTL are purged by the node-stats loop — the cache only
        # ever holds objects mid-transfer, never cold ones.
        cache = self._serve_view_cache
        entry = cache.get(hex_id)
        if entry is None:
            view = self.store.read_maybe_spilled(hex_id)
            if view is None:
                # broadcast relay: the object may be mid-pull on this
                # node — serve ranges that have already arrived
                return await self._serve_relay_chunk(
                    hex_id, p["offset"], p["length"])
            cache[hex_id] = [view, time.monotonic()]
            # cap must exceed the batched-get fan-in (8 concurrent
            # transfers from one holder is the common burst) or the LRU
            # thrashes mid-transfer entries on every insert
            while len(cache) > 16:
                cache.popitem(last=False)
        else:
            view = entry[0]
            entry[1] = time.monotonic()
            cache.move_to_end(hex_id)
        off, length = p["offset"], p["length"]
        self._chunks_served = getattr(self, "_chunks_served", 0) + 1
        await self._serve_throttle(length)
        # RawData: header + raw writer.write of the store view slice — no
        # bytes() materialization, no msgpack re-pack of the payload.
        # raylint: disable=R9 -- the serve-view cache entry above IS the
        # pin: it holds the (natively pinned) view until the TTL purge,
        # which outlives the reply write by construction
        return RawData(view[off : off + length])

    async def _serve_throttle(self, length: int) -> None:
        """Per-node upload-bandwidth cap for bulk chunk serving
        (``object_serve_bandwidth_bytes_ps``): a virtual-clock token
        bucket — each served byte advances the node's serve clock, and a
        request sleeps until its slot. Serialized per node (not per
        connection), so a broadcast root's fanout shares one simulated
        uplink the way a real NIC would."""
        bw = CONFIG.object_serve_bandwidth_bytes_ps
        if not bw or length <= 0:
            return
        loop = asyncio.get_running_loop()
        now = loop.time()
        clock = max(getattr(self, "_serve_clock", now), now)
        self._serve_clock = clock + length / bw
        if clock > now:
            await asyncio.sleep(clock - now)

    async def _serve_relay_chunk(self, hex_id: str, off: int, length: int):
        """Serve a chunk out of an in-flight pull's unsealed view — the
        broadcast-tree relay: interior nodes forward ranges while still
        receiving the rest. Waits (bounded) for the range to arrive,
        which also carries a child across this node's own admission
        delay. The bytes are copied out of the unsealed view (chunk-
        sized, one memcpy): its mmap's lifetime belongs to the transfer,
        and an abort must never invalidate a reply mid-write."""
        prog = self.pulls.active.get(hex_id)
        if prog is not None:
            ok = await prog.wait_covered(
                off, length, CONFIG.bcast_chunk_wait_s)
            if ok and prog.view is not None:
                self.pulls.bcast_relay_chunks += 1
                self.pulls.bcast_relay_bytes += length
                # copy BEFORE the bandwidth throttle sleeps: an abort
                # during the sleep nulls prog.view
                payload = bytes(prog.view[off : off + length])
                await self._serve_throttle(length)
                return RawData(payload)
        # the transfer may have sealed-and-unregistered while we waited:
        # the store is now the source of truth. The cache entry is the
        # escaping view's pin (same contract as the main serve path,
        # including its size cap and the bandwidth throttle).
        view = self.store.read_maybe_spilled(hex_id)
        if view is not None:
            cache = self._serve_view_cache
            cache[hex_id] = [view, time.monotonic()]
            while len(cache) > 16:
                cache.popitem(last=False)
            await self._serve_throttle(length)
            # raylint: disable=R9 -- pinned by the cache entry just
            # inserted (same contract as _fetch_object_chunk)
            return RawData(view[off : off + length])
        return None

    async def _get_pull_stats(self, conn: Connection, p) -> Dict:
        stats = self.pulls.stats()
        stats["chunks_served"] = getattr(self, "_chunks_served", 0)
        stats["zero_copy_puts"] = self._zero_copy_puts
        stats["spill"] = self.store.tier_stats()
        return stats

    async def _free_objects(self, conn: Connection, p: Dict) -> None:
        for hex_id in p["ids"]:
            # release the serve view (and its pin) before the store delete
            self._serve_view_cache.pop(hex_id, None)
            self.store.delete(hex_id)
            self._object_owners.pop(hex_id, None)
            self._leak_candidates.pop(hex_id, None)

    async def _pin_object(self, conn: Connection, p: Dict) -> None:
        self.store.pin(p["object_id"])

    async def _unpin_object(self, conn: Connection, p: Dict) -> None:
        self.store.unpin(p["object_id"])

    async def _restore_spilled(self, conn: Connection, p: Dict) -> bool:
        rec = _events.REC
        if rec.enabled and rec.sample():
            t0 = time.time()
            ok = self.store.restore(p["object_id"])
            trace, span = rec.new_trace()
            rec.record("spill_restore", "object", t0, time.time() - t0,
                       trace, span, 0,
                       {"obj": str(p["object_id"])[:16], "ok": bool(ok)})
        else:
            ok = self.store.restore(p["object_id"])
        if ok:
            self._restored_count = getattr(self, "_restored_count", 0) + 1
        return ok

    async def _get_store_stats(self, conn: Connection, p) -> Dict:
        return self.store.stats()

    async def _get_node_info(self, conn: Connection, p) -> Dict:
        return {
            "node_id": self.node_id,
            "tcp_port": self.tcp_port,
            "resources_total": self.resources.total.to_wire(),
            "resources_available": self.resources.available.to_wire(),
            "num_workers": len(self.workers),
            "num_idle": len(self.idle_workers),
            "cluster_view": self.cluster_view,
        }

    # ----------------------------------------------------- node reporter
    def _sample_node_stats(self) -> Dict:
        """One psutil sample + TPU duty (reference:
        dashboard/modules/reporter/reporter_agent.py:277 — per-node
        cpu/mem/disk/net stats; TPU utilization is the SURVEY §5 ask)."""
        import psutil

        vm = psutil.virtual_memory()
        try:
            disk = psutil.disk_usage(self.session_dir)
            disk_stats = {"total": disk.total, "used": disk.used,
                          "percent": disk.percent}
        except Exception:
            disk_stats = {}
        try:
            la1, la5, la15 = os.getloadavg()
        except OSError:
            la1 = la5 = la15 = 0.0
        return {
            "node_id": self.node_id,
            "time": time.time(),
            "cpu_percent": psutil.cpu_percent(interval=None),
            "cpu_count": psutil.cpu_count(),
            "load_avg": [la1, la5, la15],
            "mem_total_bytes": vm.total,
            "mem_used_bytes": vm.total - vm.available,
            "mem_percent": vm.percent,
            "disk": disk_stats,
            "num_workers": len(self.workers),
            "num_idle_workers": len(self.idle_workers),
            "object_store": self.store.stats(),
            "tpu": self._tpu_stats(),
        }

    def _tpu_stats(self) -> Dict:
        """TPU duty: a fake-topology override for tests, else allocation
        fraction from the resource ledger (chips leased / chips total —
        scheduling-level utilization; device-trace-level duty comes from
        the per-worker jax.profiler capture endpoint)."""
        fake = os.environ.get("RAY_TPU_FAKE_TPU_DUTY")
        total = self.resources.total.get("TPU")
        if not total and fake is None:
            return {}
        avail = self.resources.available.get("TPU") or 0.0
        out = {"chips_total": total or 0.0,
               "chips_in_use": (total or 0.0) - avail,
               "utilization": ((total - avail) / total) if total else 0.0}
        if fake is not None:
            out["duty_cycle_percent"] = float(fake)
        return out

    async def _node_stats_loop(self) -> None:
        import json as _json

        from ray_tpu._private.protocol import STATS as _rpc_stats

        period = max(CONFIG.metrics_report_interval_ms, 1000) / 1000
        self.node_stats: Dict = {}
        while True:
            # purge serve-view cache entries idle past ~2 ticks: a held
            # view pins its object against store eviction, so the cache
            # must never outlive the transfer burst it accelerates
            cache = self._serve_view_cache
            cutoff = time.monotonic() - 2 * period
            for hex_id in [h for h, e in cache.items() if e[1] < cutoff]:
                cache.pop(hex_id, None)
            # object-owner ledger prune (ISSUE 15): evictions bypass the
            # FreeObjects handler, so without this tick the ledger would
            # grow with cumulative traffic when the leak watchdog (whose
            # scan also prunes) is disarmed — the default. Entries get a
            # 30s settle window (a just-waited object may not be sealed
            # yet); remote-tier objects are live and keep their entry.
            if self._object_owners:
                now_wall = time.time()
                for hex_id, info in list(self._object_owners.items()):
                    if now_wall - info.get("sealed_at", 0) < 30:
                        continue
                    if self.store.spill_tier(hex_id) == "remote" or \
                            self.store.contains(hex_id):
                        continue
                    self._object_owners.pop(hex_id, None)
            try:
                self.node_stats = await asyncio.to_thread(
                    self._sample_node_stats)
                # publish as Prometheus-schema gauges through the same KV
                # pipeline user metrics ride (util/metrics.py flush_now)
                from ray_tpu.util.metrics import (
                    make_counter_snapshot, make_gauge_snapshot)

                st = self.node_stats
                tags = {"node_id": self.node_id}

                def gauge(name, desc, value):
                    return make_gauge_snapshot(name, desc, value, tags)

                def counter(name, desc, value):
                    return make_counter_snapshot(name, desc, value, tags)

                store_stats = st["object_store"]
                disk = st.get("disk") or {}
                snaps = [
                    gauge("ray_tpu_node_cpu_percent",
                          "Node CPU utilization percent.",
                          st["cpu_percent"]),
                    gauge("ray_tpu_node_cpu_count",
                          "Logical CPUs on the node.",
                          st.get("cpu_count") or 0),
                    gauge("ray_tpu_node_load_avg_1m",
                          "1-minute load average.",
                          (st.get("load_avg") or [0])[0]),
                    gauge("ray_tpu_node_mem_used_bytes",
                          "Node memory in use.", st["mem_used_bytes"]),
                    gauge("ray_tpu_node_mem_total_bytes",
                          "Node memory total.", st["mem_total_bytes"]),
                    gauge("ray_tpu_node_disk_used_bytes",
                          "Session-disk bytes used.",
                          disk.get("used", 0)),
                    gauge("ray_tpu_node_disk_total_bytes",
                          "Session-disk bytes total.",
                          disk.get("total", 0)),
                    gauge("ray_tpu_node_workers",
                          "Worker processes on the node.",
                          st["num_workers"]),
                    gauge("ray_tpu_node_idle_workers",
                          "Idle (leasable) worker processes.",
                          st["num_idle_workers"]),
                    # scheduler (reference: metric_defs.cc scheduler_*)
                    gauge("ray_tpu_scheduler_active_leases",
                          "Worker leases currently granted on the node.",
                          len(self.leases)),
                    gauge("ray_tpu_scheduler_pending_lease_requests",
                          "Lease requests queued on the node.",
                          len(self._pending_leases)),
                    gauge("ray_tpu_scheduler_leases_granted_total",
                          "Cumulative leases granted (counter semantics).",
                          self._lease_counter),
                    gauge("ray_tpu_scheduler_spillbacks_total",
                          "Lease requests redirected to other nodes.",
                          getattr(self, "_spillback_count", 0)),
                    gauge("ray_tpu_pg_bundles_reserved",
                          "Placement-group bundles reserved on the node.",
                          len(self._pg_bundles)),
                    # object plane (reference: metric_defs.cc object_store_*
                    # + object_manager_*)
                    gauge("ray_tpu_object_store_used_bytes",
                          "Object store bytes in use.",
                          store_stats.get("used", 0)),
                    gauge("ray_tpu_object_store_capacity_bytes",
                          "Object store arena capacity.",
                          store_stats.get("capacity", 0)),
                    gauge("ray_tpu_object_store_num_objects",
                          "Sealed objects resident in the store.",
                          store_stats.get("num_objects", 0)),
                    gauge("ray_tpu_object_store_evictions_total",
                          "Cumulative LRU evictions.",
                          store_stats.get("num_evictions", 0)),
                    gauge("ray_tpu_object_store_created_total",
                          "Cumulative objects created.",
                          store_stats.get("num_created", 0)),
                    gauge("ray_tpu_object_spilled_total",
                          "Objects spilled to disk.",
                          getattr(self.store, "num_spills", 0)),
                    gauge("ray_tpu_object_restored_total",
                          "Spilled objects restored.",
                          getattr(self, "_restored_count", 0)),
                    counter("ray_tpu_object_chunks_served_total",
                            "Object chunks served to remote nodes.",
                            getattr(self, "_chunks_served", 0)),
                    counter("ray_tpu_object_chunks_fetched_total",
                            "Object chunks fetched from remote nodes.",
                            self.pulls.chunks_fetched),
                    gauge("ray_tpu_object_pulls_inflight",
                          "Cross-node object pulls in progress.",
                          len(self._pulls_inflight)),
                    # pull pipeline (reference: object_manager chunk/window
                    # stats + pull_manager admission counters)
                    gauge("ray_tpu_object_pull_window_occupancy",
                          "Chunk RPCs in flight across all transfers.",
                          self.pulls.window_occupancy),
                    gauge("ray_tpu_object_pull_inflight_bytes",
                          "Unsealed pull bytes admitted on the node.",
                          self.pulls.budget.inflight),
                    gauge("ray_tpu_object_pull_queued",
                          "Transfers waiting on the pull byte budget.",
                          self.pulls.budget.queued),
                    counter("ray_tpu_object_pull_queued_total",
                            "Transfers that ever queued on the budget.",
                            self.pulls.budget.queued_total),
                    counter("ray_tpu_object_pull_bytes_total",
                            "Bytes fetched from remote nodes.",
                            self.pulls.bytes_fetched),
                    counter("ray_tpu_object_pull_stripe_failovers_total",
                            "Chunk stripes failed over to another holder.",
                            self.pulls.stripe_failovers),
                    # device object plane (ISSUE 9): zero-copy puts,
                    # broadcast-tree shape + relay volume, spill tiers
                    counter("ray_tpu_store_zero_copy_puts",
                            "Typed array objects sealed without a "
                            "pickle pass.",
                            self._zero_copy_puts),
                    gauge("ray_tpu_bcast_tree_depth",
                          "Depth of this node's latest broadcast-tree "
                          "slot.",
                          self.pulls.bcast_last_depth),
                    counter("ray_tpu_bcast_relay_bytes",
                            "Bytes relayed to children from unsealed "
                            "in-flight views.",
                            self.pulls.bcast_relay_bytes),
                    counter("ray_tpu_bcast_reparents_total",
                            "Dead broadcast parents this node reported.",
                            self.pulls.bcast_reparents_client),
                    counter("ray_tpu_object_spill_remote_total",
                            "Objects demoted to the remote-holder spill "
                            "tier.",
                            getattr(self.store, "num_remote_demotions",
                                    0)),
                    gauge("ray_tpu_object_waits_pending",
                          "Local seal-wait futures outstanding.",
                          sum(len(v) for v in self._object_waits.values())),
                    # worker pool lifecycle (reference: metric_defs.cc
                    # worker_register/worker_process series)
                    gauge("ray_tpu_worker_processes_started_total",
                          "Cumulative worker processes spawned.",
                          getattr(self, "_workers_spawned", 0)),
                    gauge("ray_tpu_worker_env_evictions_total",
                          "Idle workers killed for runtime-env mismatch.",
                          getattr(self, "_env_evictions", 0)),
                    gauge("ray_tpu_worker_starting",
                          "Worker processes spawning (pre-registration).",
                          self._starting_workers),
                    # warm worker pool (ISSUE 10)
                    gauge("ray_tpu_worker_pool_warm",
                          "Pristine pre-warmed workers parked leasable.",
                          self._warm_idle_count()),
                    counter("ray_tpu_worker_pool_hits_total",
                            "Actor starts served from the warm pool.",
                            self._pool_hits),
                    counter("ray_tpu_worker_pool_misses_total",
                            "Actor starts that fell back to a cold fork.",
                            self._pool_misses),
                    counter("ray_tpu_worker_pool_demand_hits_total",
                            "Missed actor starts served by a demand-"
                            "paged pool worker (ISSUE 11).",
                            self._demand_hits),
                    counter("ray_tpu_worker_pool_reaped_total",
                            "Warm workers reaped on the idle TTL.",
                            self._pool_reaped),
                    # RPC fabric (reference: grpc_server_* / grpc_client_*)
                    gauge("ray_tpu_rpc_frames_in_total",
                          "Control-plane frames received by this process.",
                          _rpc_stats["frames_in"]),
                    gauge("ray_tpu_rpc_frames_out_total",
                          "Control-plane frames sent by this process.",
                          _rpc_stats["frames_out"]),
                    gauge("ray_tpu_rpc_bytes_in_total",
                          "Control-plane bytes received by this process.",
                          _rpc_stats["bytes_in"]),
                    gauge("ray_tpu_rpc_bytes_out_total",
                          "Control-plane bytes sent by this process.",
                          _rpc_stats["bytes_out"]),
                ]
                # object ownership ledger (ISSUE 15): store bytes by
                # spill tier + the watchdog's current suspect count
                tiers = self.store.tier_stats()
                for tier, nbytes in (
                        ("shm", tiers.get("shm_bytes",
                                          store_stats.get("used", 0))),
                        ("disk", tiers.get("disk_bytes", 0)),
                        ("remote", tiers.get("remote_bytes", 0))):
                    snaps.append(make_gauge_snapshot(
                        "ray_tpu_store_bytes",
                        "Object store bytes held, by spill tier.",
                        nbytes,
                        {"node_id": self.node_id, "tier": tier}))
                snaps.append(gauge(
                    "ray_tpu_object_leak_suspects",
                    "Objects the leak watchdog currently flags.",
                    len(self._leak_suspects)))
                snaps.append(gauge(
                    "ray_tpu_object_leak_repairs_total",
                    "Leaked store copies freed by the watchdog repair "
                    "hook.",
                    self._leak_repairs))
                # per-resource availability (reference: resources gauge
                # per resource name)
                for rname, total_amt in self.resources.total.to_dict() \
                        .items():
                    avail = self.resources.available.get(rname) or 0.0
                    snaps.append(make_gauge_snapshot(
                        "ray_tpu_resource_in_use",
                        "Resource units leased out, by resource name.",
                        float(total_amt) - float(avail),
                        {"node_id": self.node_id, "resource": str(rname)}))
                tpu = st.get("tpu") or {}
                if tpu:
                    snaps.append(gauge(
                        "ray_tpu_tpu_utilization",
                        "Fraction of the node's TPU chips leased.",
                        tpu.get("utilization", 0.0)))
                    if "duty_cycle_percent" in tpu:
                        snaps.append(gauge(
                            "ray_tpu_tpu_duty_cycle_percent",
                            "TPU duty cycle percent.",
                            tpu["duty_cycle_percent"]))
                await self.head.call("KvPut", {
                    "key": f"metrics::{self.node_id}::agent".encode(),
                    "value": _json.dumps(snaps).encode(),
                    "ns": "_metrics", "overwrite": True},
                    timeout=CONFIG.control_rpc_timeout_s)
            except Exception:
                pass
            await asyncio.sleep(period)

    async def _get_node_stats(self, conn: Connection, p) -> Dict:
        return getattr(self, "node_stats", {}) or \
            await asyncio.to_thread(self._sample_node_stats)

    async def _list_events(self, conn: Connection, p) -> List[Dict]:
        """This node's structured events (multi-node session dirs are per
        machine; the state API aggregates across agents)."""
        from ray_tpu._private.event import read_events

        p = p or {}
        return await asyncio.to_thread(
            read_events, self.session_dir,
            severity=p.get("severity"), label=p.get("label"),
            limit=int(p.get("limit", 1000)))

    async def _list_workers(self, conn: Connection, p) -> List[Dict]:
        """Live worker-table query (reference: the state API pairs GCS data
        with NodeManager::QueryAllWorkerStates, node_manager.h:217)."""
        out = []
        for w in self.workers.values():
            if w.proc is None:
                # still parked in the spawn admission queue: there is no
                # process (and no pid) to report yet — listing it raced
                # observers that treat every row as a live worker process
                continue
            out.append({
                "worker_id": w.worker_id,
                "node_id": self.node_id,
                "pid": w.proc.pid,
                "state": ("ACTOR" if w.is_actor
                          else "LEASED" if w.leased_to else "IDLE"),
                "actor_id": w.actor_id,
                "env_key": w.env_key,
                "alive": w.alive,
                "direct_addr": w.direct_addr,
            })
        return out

    async def _list_store_objects(self, conn: Connection, p) -> List[Dict]:
        """Per-node object-store contents (reference: list_objects in
        util/state/api.py aggregating core-worker object views)."""
        limit = int(p.get("limit", 1000)) if isinstance(p, dict) else 1000
        return [dict(row, node_id=self.node_id)
                for row in self.store.list_entries(limit)]

    # ------------------------------------ object introspection (ISSUE 15)
    def _introspect_targets(self) -> List[Dict]:
        """Direct addrs of every local process with a ref table: the
        registered drivers plus the live registered workers."""
        targets: List[Dict] = []
        seen = set()
        for info in list(self._driver_clients.values()):
            addr = info.get("direct_addr") or {}
            key = (addr.get("host"), addr.get("port"))
            if addr.get("port") and key not in seen:
                seen.add(key)
                targets.append(addr)
        for w in list(self.workers.values()):
            addr = w.direct_addr or {}
            key = (addr.get("host"), addr.get("port"))
            if (w.alive and w.registered.is_set() and addr.get("port")
                    and key not in seen):
                seen.add(key)
                targets.append(addr)
        return targets

    async def _call_local_process(self, addr: Dict, payload: Dict):
        client = await self.pool.get(addr["host"], addr["port"])
        return await client.call(
            "GetObjectRefs", payload,
            timeout=CONFIG.object_introspect_timeout_s)

    async def _gather_local_ref_dumps(self, limit: int) -> List[Dict]:
        targets = self._introspect_targets()

        async def one(addr: Dict) -> Dict:
            try:
                return await self._call_local_process(addr,
                                                      {"limit": limit})
            except Exception as e:
                return {"error": f"{type(e).__name__}: {e}",
                        "addr": {"host": addr.get("host"),
                                 "port": addr.get("port")}}

        return list(await asyncio.gather(*(one(a) for a in targets)))

    async def _get_object_refs(self, conn: Connection, p) -> Dict:
        """Node-wide object introspection: store tier usage + every local
        process's ref tables with provenance + the watchdog's current
        leak suspects. The head's ObjectSummary fans this out."""
        p = p or {}
        limit = int(p.get("limit", 10000))
        objects = []
        for row in self.store.list_entries(limit):
            info = self._object_owners.get(row["object_id"])
            row = dict(row, node_id=self.node_id)
            if info:
                row["owner"] = {"host": info["owner"].get("host"),
                                "port": info["owner"].get("port")}
                row["creator_task"] = info.get("task") or ""
                row["creator_callsite"] = info.get("callsite") or ""
            objects.append(row)
        return {
            "node_id": self.node_id,
            "store": self.store.stats(),
            "tiers": self.store.tier_stats(),
            "objects": objects,
            "processes": await self._gather_local_ref_dumps(limit),
            "leak_suspects": list(self._leak_suspects),
            "leak_scans": self._leak_scans,
            "leak_repairs": self._leak_repairs,
        }

    async def _leak_watchdog_loop(self) -> None:
        """Default-off leak scan (``object_leak_scan_interval_s`` > 0
        arms it at boot): every interval, interrogate each big sealed
        object's OWNER — an object whose owner reports zero local refs /
        borrowers / task pins (or no longer knows it) yet that remains
        unevicted past ``object_leak_grace_s`` is a leak suspect, as is a
        borrower entry whose owner no longer lists the borrow."""
        while not self._closing:
            interval = float(CONFIG.object_leak_scan_interval_s)
            await asyncio.sleep(interval if interval > 0 else 2.0)
            if interval <= 0:
                continue
            try:
                await self._scan_for_leaks()
            except Exception:
                logging.getLogger("ray_tpu").exception("leak scan failed")

    async def _scan_for_leaks(self) -> List[Dict]:
        min_bytes = int(CONFIG.object_leak_min_bytes)
        grace = float(CONFIG.object_leak_grace_s)
        now = time.time()
        self._leak_scans += 1
        all_entries = self.store.list_entries(100000)
        # remote-tier entries hold no local bytes but ARE still live and
        # restorable: keep their owner attribution, just don't scan them
        present = {row["object_id"] for row in all_entries}
        entries = {row["object_id"]: row for row in all_entries
                   if row.get("tier") != "remote"}
        # the ledger tracks only what the store still holds (any tier)
        for hex_id in [h for h in self._object_owners if h not in present]:
            self._object_owners.pop(hex_id, None)
        # -- big sealed objects, batched one owner round trip per owner
        by_owner: Dict[tuple, List[str]] = {}
        owner_addr: Dict[tuple, Dict] = {}
        for hex_id, row in entries.items():
            if row["size_bytes"] < min_bytes:
                continue
            info = self._object_owners.get(hex_id)
            if not info or not info.get("owner"):
                continue
            key = (info["owner"].get("host"), info["owner"].get("port"))
            owner_addr[key] = info["owner"]
            by_owner.setdefault(key, []).append(hex_id)
        candidates: Dict[str, Dict] = {}

        def add_candidate(key: str, row: Dict) -> None:
            candidates[key] = row

        for key, ids in by_owner.items():
            try:
                reply = await self._call_local_process(
                    owner_addr[key], {"ids": ids})
                refs = reply.get("refs", {})
            except Exception:
                # owner process gone: every big object it owned that the
                # store still holds is orphaned by definition
                for h in ids:
                    add_candidate(h, {
                        "object_id": h, "reason": "owner_unreachable",
                        "size_bytes": entries[h]["size_bytes"],
                        "tier": entries[h]["tier"],
                        "pinned": bool(entries[h].get("pinned")),
                        "callsite": "", "creator": ""})
                continue
            for h in ids:
                v = refs.get(h) or {}
                dropped = not v.get("owned") or v.get("state") == "freed"
                zero_refs = (v.get("local_refs", 0) <= 0
                             and v.get("borrowers", 0) <= 0
                             and v.get("task_pins", 0) <= 0)
                if not (dropped or zero_refs):
                    continue
                add_candidate(h, {
                    "object_id": h,
                    "reason": "owner_dropped" if dropped else "zero_refs",
                    "size_bytes": entries[h]["size_bytes"],
                    "tier": entries[h]["tier"],
                    "pinned": bool(entries[h].get("pinned")),
                    "callsite": v.get("callsite", ""),
                    "creator": v.get("creator", "")})
        # -- orphan borrowers: local borrow entries the owner forgot.
        # Batched like the sealed-object pass: ONE ref_info RPC per
        # distinct owner, not one per borrowed entry.
        borrow_rows: Dict[tuple, List[Tuple[Dict, int]]] = {}
        borrow_owner: Dict[tuple, Dict] = {}
        for dump in await self._gather_local_ref_dumps(10000):
            for row in dump.get("borrowed") or []:
                owner = row.get("owner") or {}
                if not owner.get("port"):
                    continue
                key = (owner.get("host"), owner.get("port"))
                borrow_owner[key] = owner
                borrow_rows.setdefault(key, []).append(
                    (row, dump.get("pid", 0)))
        for key, rows in borrow_rows.items():
            ids = sorted({row["object_id"] for row, _pid in rows})
            try:
                reply = await self._call_local_process(
                    borrow_owner[key], {"ids": ids})
                refs = reply.get("refs") or {}
            except Exception:
                refs = {}
            for row, pid in rows:
                v = refs.get(row["object_id"]) or {}
                if v.get("owned") and v.get("state") != "freed" \
                        and v.get("borrowers", 0) > 0:
                    continue
                add_candidate("borrow:" + row["object_id"], {
                    "object_id": row["object_id"],
                    "reason": "orphan_borrow",
                    "size_bytes": v.get("size_bytes", 0),
                    "tier": "", "pinned": False,
                    "callsite": v.get("callsite", ""),
                    "creator": v.get("creator", ""),
                    "borrower_pid": pid})
        # -- grace accounting: a candidate first seen on an EARLIER scan
        # and older than the grace graduates to suspect
        for stale in [k for k in self._leak_candidates
                      if k not in candidates]:
            self._leak_candidates.pop(stale, None)
        suspects: List[Dict] = []
        for key, row in candidates.items():
            first = self._leak_candidates.setdefault(key, now)
            if first < now and now - first >= grace:
                suspects.append(dict(row, age_s=round(now - first, 1)))
        prev = {s["object_id"] + s["reason"] for s in self._leak_suspects}
        self._repair_leaks(suspects, now)
        self._leak_suspects = suspects
        rec = _events.REC
        if rec.enabled:
            for s in suspects:
                if s["object_id"] + s["reason"] in prev:
                    continue  # already on the timeline
                trace, span = rec.new_trace()
                rec.record("leak_suspect", "object", now, 0.0, trace,
                           span, 0,
                           {"obj": s["object_id"][:16],
                            "bytes": s["size_bytes"],
                            "reason": s["reason"],
                            "callsite": s.get("callsite", "")[:64]})
        return suspects

    def _repair_leaks(self, suspects: List[Dict], now: float) -> None:
        """Repair hook (ISSUE 17): a graduated ``owner_unreachable`` /
        ``zero_refs`` suspect is garbage by definition — its owner can
        never serve another pull (process gone) or holds no reference
        that could reach the bytes again. Free the local store copy
        instead of merely reporting it; the verdict already survived the
        scan grace, so a transient owner blip cannot trip this.
        ``orphan_borrow`` stays report-only: those bytes live in a remote
        process's memory store, not this node's object store."""
        rec = _events.REC
        for s in suspects:
            if s.get("reason") not in ("owner_unreachable", "zero_refs"):
                continue
            hex_id = s.get("object_id") or ""
            if not hex_id or not (self.store.contains(hex_id)
                                  or self.store.is_spilled(hex_id)):
                continue
            self.store.delete(hex_id)
            self._object_owners.pop(hex_id, None)
            self._leak_repairs += 1
            s["repaired"] = True
            if rec.enabled:
                trace, span = rec.new_trace()
                rec.record("leak_repair", "object", now, 0.0, trace, span,
                           0, {"obj": hex_id[:16],
                               "bytes": s.get("size_bytes", 0),
                               "reason": s.get("reason", "")})

    async def _set_resource(self, conn: Connection, p: Dict) -> Dict:
        """Dynamically re-declare a custom resource's total (reference:
        experimental/dynamic_resources.py set_resource). The available
        amount shifts by the same delta, so in-flight leases keep their
        accounting."""
        name = p["resource"]
        new_total = float(p["capacity"])
        delta = new_total - self.resources.total.get(name)
        shift = ResourceSet({name: abs(delta)})
        if delta >= 0:
            self.resources.total.add(shift)
            self.resources.available.add(shift)
        else:
            self.resources.total.subtract(shift, allow_negative=True)
            self.resources.available.subtract(shift, allow_negative=True)
        self._resources_dirty = True
        await self._drain_pending_leases()
        return {"total": self.resources.total.get(name)}


class _ForeignProc:
    """Stand-in Popen for worker processes the agent didn't spawn."""

    def __init__(self, pid: int):
        self.pid = pid

    def poll(self):
        if not self.pid:
            return None
        try:
            os.kill(self.pid, 0)
            return None
        except OSError:
            return 1

    def terminate(self):
        if self.pid:
            try:
                os.kill(self.pid, 15)
            except OSError:
                pass

    def kill(self):
        if self.pid:
            try:
                os.kill(self.pid, 9)
            except OSError:
                pass


def main() -> None:
    import argparse
    import json

    from ray_tpu._private import sanitizer as _sanitizer

    _sanitizer.maybe_install()
    parser = argparse.ArgumentParser()
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--store-dir", required=True)
    parser.add_argument("--head-host", required=True)
    parser.add_argument("--head-port", type=int, required=True)
    parser.add_argument("--resources", required=True)  # json
    parser.add_argument("--labels", default="{}")
    parser.add_argument("--object-store-memory", type=int, default=0)
    parser.add_argument("--ready-file", default="")
    args = parser.parse_args()

    async def run():
        import signal

        from ray_tpu._private import proc_profile

        lifecycle.register_self("agent", args.session_dir, args.node_id)
        # chaos rules target processes by node id (workers inherit it via
        # RAY_TPU_NODE_ID; the agent gets its id as an argv flag)
        set_fault_self_id(args.node_id)
        prof = proc_profile.maybe_start()
        agent = NodeAgent(
            node_id=args.node_id,
            session_dir=args.session_dir,
            store_dir=args.store_dir,
            head_host=args.head_host,
            head_port=args.head_port,
            resources=json.loads(args.resources),
            labels=json.loads(args.labels),
            object_store_memory=args.object_store_memory or None,
        )
        # a crashed/SIGKILL'd spawner (driver or CLI runner) must strand
        # nothing: SIGTERM lands here, the handler below tears workers down
        lifecycle.fate_share_with_parent()
        await agent.start()
        if args.ready_file:
            with open(args.ready_file, "w") as f:
                f.write(json.dumps({"unix_path": agent.unix_path,
                                    "tcp_port": agent.tcp_port}))
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):
            pass
        await stop.wait()
        from ray_tpu._private import events as _ev

        _ev.REC.dump_local("sigterm")
        # close RPC clients cleanly (cancel + await read loops) BEFORE the
        # loop dies: a close() here would strand cancelled tasks and spray
        # "Task was destroyed but it is pending!" into the agent log the
        # log monitor streams to the driver
        try:
            await asyncio.wait_for(agent.aclose_clients(), timeout=2)
        except Exception:
            pass
        # guaranteed teardown: the agent owns its node's process tree
        await asyncio.to_thread(agent.teardown_processes)
        proc_profile.dump(prof, "agent")
        lifecycle.unregister_process(args.session_dir, os.getpid())

    asyncio.run(run())


if __name__ == "__main__":
    main()
