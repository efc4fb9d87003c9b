"""Head-node control plane (GCS analog).

Parity with the reference's GCS server (reference:
``src/ray/gcs/gcs_server/gcs_server.h``): node membership + health
(GcsNodeManager / GcsHealthCheckManager), actor registry + scheduling
(GcsActorManager/GcsActorScheduler), placement groups
(GcsPlacementGroupManager), internal KV (GcsInternalKVManager), job table
(GcsJobManager), pubsub, and an aggregated cluster resource view
(GcsResourceManager) that is gossiped back to node agents for spillback
decisions (ray_syncer analog).

One asyncio process, TCP. State is in-memory; durability is layered
(reference: gcs_server.cc storage-backend selection):

* **File-backed (default when ``RAY_TPU_GCS_PERSIST`` is a path):** every
  authoritative mutation is write-ahead logged (``wal.py``) and the
  mutating RPC replies only after the record is fsynced — a ``kill -9``
  at ANY point loses nothing that was acked. Snapshot-and-truncate
  compaction bounds the log; recovery replays snapshot + log suffix.
* **Redis-backed:** the debounced full-snapshot save (the external store
  outlives the head; per-mutation round trips would serialize the loop).

Recovery does not trust the restored tables blindly: restored nodes and
actors enter a ``RECOVERING`` state with a claim window
(``gcs_recovery_grace_s``). Agents re-register into their existing
incarnations — reporting which actors they still actually host — to
claim them; drivers re-register to claim their jobs. Anything unclaimed
at window close is declared dead through the normal death machinery with
reason ``lost_during_head_outage``: no ghost actors, no zombie nodes.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import logging
import os
import time
import bisect
from collections import deque
from typing import Any, Dict, List, Optional, Set, Tuple

from ray_tpu._private.config import CONFIG
from ray_tpu._private.protocol import Connection, RpcServer
from ray_tpu._private.resources import (
    NodeResources, ResourceSet, label_constraints_match)

ACTOR_PENDING = "PENDING_CREATION"
ACTOR_ALIVE = "ALIVE"
ACTOR_RESTARTING = "RESTARTING"
ACTOR_DEAD = "DEAD"
# restored from the durable store after a head restart; waiting for its
# node's agent to re-register and claim it within the recovery window
ACTOR_RECOVERING = "RECOVERING"

# reason string for entities reconciled dead at recovery-window close;
# tests and operators match on it EXACTLY (DeathContext.reason)
LOST_DURING_HEAD_OUTAGE = "lost_during_head_outage"

# Dead-entry cache caps (reference: maximum_gcs_dead_node_cached_count /
# maximum_gcs_destroyed_actor_cached_count): dead nodes/actors stay
# queryable for post-mortems, but churn must bound to live+cache, never
# grow with cumulative cluster history (raylint R10).
_DEAD_NODE_CACHE = 256
_DEAD_ACTOR_CACHE = 1024


class _RestoredConn:
    """Placeholder connection for entities restored from the durable
    store: permanently closed, so every push/broadcast no-ops until the
    real agent/driver re-registers and swaps in a live connection."""

    closed = True

    def __init__(self):
        self.meta: Dict = {}

    async def push(self, method: str, payload: Any) -> None:
        pass

    async def send(self, msg: Any) -> None:
        pass

    def close(self) -> None:
        pass


class NodeInfo:
    def __init__(self, node_id: str, addr: Dict, resources: NodeResources,
                 conn: Connection, incarnation: int = 0):
        self.node_id = node_id
        self.addr = addr  # {"host":..., "port":...} of the agent's TCP server
        self.resources = resources
        self.conn = conn
        self.alive = True
        # per-boot monotonic stamp from the agent; fenced on death so a
        # partition survivor re-registering the SAME incarnation is
        # rejected (a fresh agent process carries a higher one)
        self.incarnation = incarnation
        self.last_heartbeat = time.monotonic()
        # set while the agent's connection is down but the reconnect
        # grace window is still open
        self.disconnected_at: Optional[float] = None
        # restored from the durable store after a head restart; cleared
        # when the agent re-registers (claims it) within the recovery
        # window, else the node is reconciled dead
        self.recovering = False
        self.labels = resources.labels
        self.pending_demand: List[Dict] = []  # unfulfilled lease requests
        # version of the last full resource snapshot applied; heartbeats
        # carrying a different version mean this head's view is stale
        # (head restart / missed report) and trigger a resync
        self.resource_version = 0


class ActorInfo:
    def __init__(self, actor_id: str, spec_wire: Dict, name: str, namespace: str,
                 max_restarts: int, owner_conn: Optional[Connection]):
        self.actor_id = actor_id
        self.spec_wire = spec_wire
        self.name = name
        self.namespace = namespace
        self.state = ACTOR_PENDING
        self.node_id: Optional[str] = None
        self.addr: Optional[Dict] = None  # worker's direct call address
        self.max_restarts = max_restarts
        self.num_restarts = 0
        self.death_cause = ""
        # structured failure provenance: (unix_time, event) transitions +
        # the death's node/incarnation, shipped in every actor event so
        # caller-side ActorDiedError carries the full story
        self.timeline: List = [(time.time(), "created")]
        self.death_node_id: str = ""
        self.death_incarnation: int = 0
        self.owner_conn = owner_conn
        self.owner_job: Optional[str] = None  # job_id of the owning driver
        self.detached = bool(spec_wire.get("detached"))
        self.class_name = spec_wire.get("class_name", "")
        self.pid: int = 0
        # True between restore-from-durable-store and the hosting agent's
        # claiming re-register (recovery reconciliation)
        self.recovering = False

    def note(self, event: str) -> None:
        self.timeline.append((time.time(), event))
        if len(self.timeline) > 20:  # bounded: restart loops must not grow it
            self.timeline = self.timeline[:1] + self.timeline[-19:]

    def public_view(self) -> Dict:
        return {
            "actor_id": self.actor_id,
            "state": self.state,
            "node_id": self.node_id,
            "addr": self.addr,
            "name": self.name,
            "namespace": self.namespace,
            "class_name": self.class_name,
            "num_restarts": self.num_restarts,
            "death_cause": self.death_cause,
            "death_context": {
                "node_id": self.death_node_id or (self.node_id or ""),
                "incarnation": self.death_incarnation,
                "reason": self.death_cause,
                "timeline": [list(ev) for ev in self.timeline],
            },
            "pid": self.pid,
        }


class _NodeRank:
    """Utilization-ordered index of schedulable nodes (ISSUE 10).

    Maintained incrementally on node deltas (register / resource report /
    death / recovery), so a placement walks candidates in
    least-utilized-first order and stops at the first fit — per-placement
    cost no longer pays a full sort of every alive node. Updates are
    O(log n) to locate + O(n) list splice, paid per *node event*; the
    hot path (a 1,000-actor creation burst) is placements, not node
    events."""

    def __init__(self):
        self._keys: List[Tuple[float, str]] = []  # sorted (util, node_id)
        self._cur: Dict[str, Tuple[float, str]] = {}

    def update(self, node_id: str, util: float) -> None:
        self.remove(node_id)
        key = (util, node_id)
        bisect.insort(self._keys, key)
        self._cur[node_id] = key

    def remove(self, node_id: str) -> None:
        key = self._cur.pop(node_id, None)
        if key is not None:
            i = bisect.bisect_left(self._keys, key)
            if i < len(self._keys) and self._keys[i] == key:
                self._keys.pop(i)

    def __contains__(self, node_id: str) -> bool:
        return node_id in self._cur

    def __len__(self) -> int:
        return len(self._cur)

    def ordered_ids(self) -> List[str]:
        return [node_id for _util, node_id in self._keys]


class HeadServer:
    """The cluster brain. All state lives here; agents and drivers connect in."""

    def __init__(self, session_dir: str, port: int = 0,
                 persist_path: Optional[str] = None):
        self.session_dir = session_dir
        self.port = port
        self.server = RpcServer("head")
        # dead entries are CACHED, not kept forever: pruned past
        # _DEAD_NODE_CACHE / _DEAD_ACTOR_CACHE below (reference:
        # maximum_gcs_dead_node_cached_count /
        # maximum_gcs_destroyed_actor_cached_count) — node/actor churn
        # must not grow the head with cumulative, rather than live, state
        self.nodes: Dict[str, NodeInfo] = {}
        # node_id -> highest fenced incarnation: dead incarnations may
        # never rejoin (their leases/objects were already declared lost)
        self.fenced_incarnations: Dict[str, int] = {}
        # loop name -> restart count (ray_tpu_gcs_loop_restarts); keyed
        # by the ~6 static supervisor loop names, bounded by construction
        # raylint: disable=R10 -- bounded: keys are the fixed loop names
        self.loop_restarts: Dict[str, int] = {}
        self.report_stats = {}
        self.actors: Dict[str, ActorInfo] = {}
        self.named_actors: Dict[tuple, str] = {}  # (namespace, name) -> actor_id
        self.kv: Dict[str, Dict[bytes, bytes]] = {}  # namespace -> key -> value
        self.jobs: Dict[str, Dict] = {}
        self.placement_groups: Dict[str, Dict] = {}
        # ---- O(1) incremental scheduler state (ISSUE 10) ----
        # Per-node committed-resources ledger: in-flight placements
        # (StartActor pushed, not yet ready) counted against a candidate's
        # advertised availability. Insertion-ordered per node so age-out
        # prunes from the front; entries leave on ready/death. Replaces
        # both the full-cluster actor scan (pre-round-5) and the
        # _recent_placements deque with its per-placement dedupe pass.
        self._committed_nodes: Dict[str, Dict[str, Tuple[float, ResourceSet]]] = {}
        self._committed_agg: Dict[str, ResourceSet] = {}
        self._committed_node_of: Dict[str, str] = {}  # actor_id -> node_id
        # actor indexes maintained on every state/node transition, so node
        # death/claim/driver-exit cascades and the metrics loop stop
        # scanning the whole actor table per event
        self._actors_by_node: Dict[str, Set[str]] = {}
        self._actors_by_job: Dict[Optional[str], Set[str]] = {}
        self._actor_state_counts: Dict[str, int] = {}
        # schedulable nodes (alive, claimed) ranked by utilization:
        # candidate selection walks this in order and stops at the first
        # fit instead of re-sorting every alive node per placement
        self._node_rank = _NodeRank()
        self.subscribers: Dict[str, set] = {}  # channel -> set[Connection]
        # broadcast-tree coordination (device object plane, ISSUE 9):
        # transient transfer topology, deliberately NOT WAL-durable — a
        # restarted head starts fresh trees and mid-flight consumers
        # degrade to plain pulls
        from ray_tpu._private.broadcast import BcastTreeRegistry

        self.bcast = BcastTreeRegistry()
        # task state-transition ring: deque(maxlen) makes overflow an O(1)
        # popleft per append instead of the old O(n) list copy on EVERY
        # overflowing flush (the buffered-count gauge reads len() as before)
        self.task_events: deque = deque(
            maxlen=max(1, int(CONFIG.task_event_buffer_max)))
        # flight-recorder span ring (ISSUE 14): flushed per-process rings
        # land here; ListSpans/timeline read it
        self.span_events: deque = deque(
            maxlen=max(1, int(CONFIG.task_event_span_buffer_max)))
        self.span_events_total = 0  # appended ever (drop gauge = total-len)
        # per-node flight-recorder flush stats: node_id -> {events, spans,
        # flushes, last_flush, rings: {role-pid: ring stats}}
        self.event_node_stats: Dict[str, Dict] = {}
        self.cluster_config = CONFIG.snapshot()
        self._pg_counter = 0
        # GCS fault tolerance (reference: storage backend selected at
        # gcs_server.cc:522-535 — in-memory vs RedisStoreClient HA):
        # durable state goes through a pluggable StoreClient (a file, or
        # an external redis:// store that outlives this head); a restarted
        # head with the same URI resumes KV/jobs/actors/PGs while agents +
        # drivers re-register through their watchdogs
        # (NodeManagerService.NotifyGCSRestart analog).
        self.persist_path = persist_path
        self.store = None
        self.wal = None
        self.started_at = time.time()
        # per-boot head generation: restored+1 on every recovery, so
        # operators (CLI status) can see how many lives this head has had
        self.head_incarnation = 1
        # recovery reconciliation bookkeeping (claim window)
        self.recovering_nodes: set = set()
        self.recovering_actors: set = set()
        self.recovering_jobs: set = set()
        self.last_recovery: Dict[str, Any] = {}
        self._compacting = False
        if persist_path:
            from ray_tpu._private.store_client import create_store_client

            self.store = create_store_client(persist_path)
            # WAL rides next to a file-backed snapshot: per-mutation
            # durability with group-commit fsync. Redis mode keeps the
            # debounced snapshot (the external store outlives the head).
            if not persist_path.startswith(("redis://", "rediss://")):
                from ray_tpu._private.wal import WriteAheadLog

                self.wal = WriteAheadLog(
                    persist_path + ".wal",
                    fsync_interval_ms=CONFIG.gcs_wal_fsync_interval_ms)
        self._save_pending = False
        self._save_lock = asyncio.Lock()
        self._driver_conns: Dict[Optional[str], Connection] = {}
        if self.store is not None:
            self._load_state()
        # Strong refs to background tasks: the loop only holds weak refs, so
        # an unreferenced retry task can be GC'd mid-flight (asyncio docs).
        self._bg_tasks: set = set()
        self._register_routes()

    # ------------------------------------------------------- persistence
    def _load_state(self) -> None:
        import pickle

        # A load failure must be FATAL, not "start empty": the next
        # durable write would overwrite the store with an empty snapshot,
        # destroying exactly the state HA exists to protect (e.g. a
        # transient redis outage during head restart).
        tables = self.store.load()
        if tables and all(isinstance(v, bytes) for v in tables.values()):
            state = {name: pickle.loads(blob)
                     for name, blob in tables.items()}
        else:
            # legacy file snapshot: one pickle of the state dict itself
            state = tables
        snapshot_seq = int(state.get("seq", 0)) if state else 0
        if state:
            self._apply_snapshot(state)
        wal_records = 0
        if self.wal is not None:
            # crash-consistent replay off the WAL's open-time scan (one
            # read of the file, torn tail already truncated, stopped at
            # the first bad CRC — a head killed mid-write must never
            # crash-loop on its own log)
            records = [r for r in self.wal.take_boot_records()
                       if r[0] > snapshot_seq]
            for _seq, op, data in records:
                try:
                    self._apply_wal_op(op, data)
                except Exception:
                    logging.getLogger("ray_tpu").exception(
                        "skipping unreplayable WAL op %r", op)
            wal_records = len(records)
            self.wal.reset_seq(snapshot_seq)
        if not state and not wal_records:
            return
        self.head_incarnation += 1
        self._begin_recovery(wal_records)
        # snapshot restore + WAL replay mutate ActorInfo/NodeInfo fields
        # directly; derive the incremental scheduler indexes once here
        self._rebuild_actor_indexes()
        for node in self.nodes.values():
            self._rank_update(node)

    def _apply_snapshot(self, state: Dict) -> None:
        self.kv = state.get("kv", {})
        self.jobs = state.get("jobs", {})
        self.named_actors = {tuple(k): v for k, v in
                             state.get("named_actors", [])}
        self.placement_groups = state.get("placement_groups", {})
        self._pg_counter = state.get("pg_counter", 0)
        self.fenced_incarnations = {
            k: int(v) for k, v in
            (state.get("fenced_incarnations") or {}).items()}
        self.head_incarnation = int(state.get("head_incarnation", 1))
        for rec in state.get("actors", []):
            self._restore_actor(rec)
        for rec in state.get("nodes", []):
            self._restore_node(rec)

    def _restore_actor(self, rec: Dict) -> None:
        info = ActorInfo(rec["actor_id"], rec["spec_wire"],
                         rec["name"], rec["namespace"],
                         rec["max_restarts"], None)
        info.state = rec["state"]
        info.addr = rec["addr"]
        info.node_id = rec["node_id"]
        info.num_restarts = rec["num_restarts"]
        info.owner_job = rec.get("owner_job")
        info.death_cause = rec.get("death_cause", "")
        info.pid = rec.get("pid", 0)
        self.actors[rec["actor_id"]] = info

    def _restore_node(self, rec: Dict) -> None:
        info = NodeInfo(rec["node_id"], rec["addr"],
                        NodeResources.from_wire(rec["resources"]),
                        _RestoredConn(),
                        incarnation=int(rec.get("incarnation", 0)))
        info.alive = bool(rec.get("alive", True))
        self.nodes[rec["node_id"]] = info

    def _apply_wal_op(self, op: str, data: Dict) -> None:
        """Replay one logged mutation. Must stay a pure, deterministic
        state transform: compaction correctness is literally
        ``replay(snapshot + suffix) == replay(full log)``."""
        if op == "kv_put":
            ns = self.kv.setdefault(data.get("ns", "default"), {})
            if data.get("overwrite", True) or data["key"] not in ns:
                ns[data["key"]] = data["value"]
        elif op == "kv_del":
            ns = self.kv.get(data.get("ns", "default"), {})
            if data.get("prefix"):
                for k in [k for k in ns if k.startswith(data["key"])]:
                    del ns[k]
            else:
                ns.pop(data["key"], None)
        elif op == "job":
            self.jobs[data["key"]] = data["job"]
        elif op == "actor_create":
            self._restore_actor(data)
            if data.get("name"):
                self.named_actors[(data["namespace"], data["name"])] = \
                    data["actor_id"]
        elif op == "actor_update":
            info = self.actors.get(data["actor_id"])
            if info is None:
                return
            for field in ("state", "addr", "node_id", "num_restarts",
                          "death_cause", "pid", "max_restarts"):
                if field in data:
                    setattr(info, field, data[field])
            if data.get("drop_name") and self.named_actors.get(
                    (info.namespace, info.name)) == info.actor_id:
                del self.named_actors[(info.namespace, info.name)]
        elif op == "node_register":
            self._restore_node(data)
        elif op == "node_dead":
            node = self.nodes.get(data["node_id"])
            if node is not None:
                node.alive = False
                node.recovering = False
            self.fenced_incarnations[data["node_id"]] = max(
                self.fenced_incarnations.get(data["node_id"], -1),
                int(data.get("incarnation", 0)))
        elif op == "pg":
            self.placement_groups[data["pg"]["pg_id"]] = data["pg"]
        elif op == "pg_remove":
            pg = self.placement_groups.get(data["pg_id"])
            if pg is not None:
                pg["state"] = "REMOVED"
        elif op == "head_boot":
            self.head_incarnation = max(self.head_incarnation,
                                        int(data.get("incarnation", 1)))

    def _begin_recovery(self, wal_records: int) -> None:
        """Mark restored entities RECOVERING: nothing restored from disk
        is trusted as alive until its agent/driver re-registers and
        claims it inside the ``gcs_recovery_grace_s`` window."""
        restored_nodes = restored_actors = 0
        for node in self.nodes.values():
            if node.alive:
                node.recovering = True
                self.recovering_nodes.add(node.node_id)
                restored_nodes += 1
        for info in self.actors.values():
            if info.state == ACTOR_ALIVE:
                # claimable: its worker may still be running; the hosting
                # agent's re-register reports whether it actually is
                info.state = ACTOR_RECOVERING
                info.recovering = True
                info.note("restored; awaiting agent claim")
                self.recovering_actors.add(info.actor_id)
                restored_actors += 1
            elif info.state in (ACTOR_PENDING, ACTOR_RESTARTING):
                # never acked running: rescheduled from scratch once the
                # claim window lets agents re-register (start() re-arms
                # the retry loop snapshots cannot persist)
                info.note("restored mid-scheduling")
        for job_id, job in self.jobs.items():
            if job.get("state") == "RUNNING":
                self.recovering_jobs.add(job_id)
        self.last_recovery = {
            "at": time.time(),
            "wal_records_replayed": wal_records,
            "restored_nodes": restored_nodes,
            "restored_actors": restored_actors,
            "restored_jobs": len(self.recovering_jobs),
            "reconciled_dead": 0,
            "completed": False,
        }

    async def _recovery_reconcile(self) -> None:
        """Close the claim window: anything restored but unclaimed is
        declared dead through the normal death machinery with reason
        ``lost_during_head_outage`` — no ghost actors, no zombie nodes,
        no immortal jobs."""
        await asyncio.sleep(float(CONFIG.gcs_recovery_grace_s))
        reconciled = 0
        # actors first so each carries the EXACT outage reason instead of
        # the node-death cascade's prefixed one
        for actor_id in list(self.recovering_actors):
            info = self.actors.get(actor_id)
            self.recovering_actors.discard(actor_id)
            if info is None or not info.recovering:
                continue
            info.recovering = False
            if info.state != ACTOR_RECOVERING:
                continue
            info.death_node_id = info.node_id or ""
            info.note("unclaimed at recovery-window close")
            await self._handle_actor_failure(info, LOST_DURING_HEAD_OUTAGE)
            reconciled += 1
        for node_id in list(self.recovering_nodes):
            node = self.nodes.get(node_id)
            self.recovering_nodes.discard(node_id)
            if node is None or not node.recovering or not node.alive:
                continue
            await self._mark_node_dead(node, LOST_DURING_HEAD_OUTAGE)
            reconciled += 1
        for job_id in list(self.recovering_jobs):
            self.recovering_jobs.discard(job_id)
            if self._driver_conns.get(job_id) is not None:
                continue  # driver re-registered (claimed) meanwhile
            job = self.jobs.get(job_id)
            if job is not None and job.get("state") == "RUNNING":
                job["state"] = "FINISHED"
                await self._durable("job", {"key": job_id, "job": dict(job)})
                reconciled += 1
            # its non-detached actors die with the lost driver
            for actor_id in list(self._actors_by_job.get(job_id, ())):
                actor = self.actors.get(actor_id)
                if actor is not None and not actor.detached \
                        and actor.owner_conn is None \
                        and actor.state != ACTOR_DEAD:
                    await self._kill_actor_internal(
                        actor, LOST_DURING_HEAD_OUTAGE)
                    reconciled += 1
        self.last_recovery["reconciled_dead"] = reconciled
        self.last_recovery["completed"] = True
        self.last_recovery["window_closed_at"] = time.time()
        if reconciled:
            from ray_tpu._private.event import report_event

            report_event(
                "WARNING", "RECOVERY_RECONCILED",
                f"declared {reconciled} unclaimed entities dead "
                f"({LOST_DURING_HEAD_OUTAGE})", reconciled=reconciled)

    async def _claim_node(self, node: NodeInfo, reported_actors) -> None:
        """An agent re-registered into its restored incarnation: the node
        is claimed, and its RECOVERING actors reconcile against the list
        the agent ACTUALLY still hosts — present means alive, absent
        means the worker died during the head outage."""
        node.recovering = False
        self.recovering_nodes.discard(node.node_id)
        self._rank_update(node)
        reported = set(reported_actors or [])
        claimed: List[ActorInfo] = []
        lost: List[ActorInfo] = []
        for actor_id in list(self._actors_by_node.get(node.node_id, ())):
            actor = self.actors.get(actor_id)
            if actor is None or not actor.recovering:
                continue
            actor.recovering = False
            self.recovering_actors.discard(actor.actor_id)
            if actor.state != ACTOR_RECOVERING:
                continue
            if actor.actor_id in reported:
                self._actor_set_state(actor, ACTOR_ALIVE)
                actor.note("claimed by re-registered agent")
                claimed.append(actor)
            else:
                actor.death_node_id = node.node_id
                actor.death_incarnation = node.incarnation
                actor.note("not in re-registering agent's live set")
                lost.append(actor)
        # one group commit for the whole claimed set: a 1000-actor node's
        # re-register must not pay 1000 serial fsync windows inside its
        # RegisterNode deadline
        await self._durable_batch([
            ("actor_update", {"actor_id": a.actor_id, "state": ACTOR_ALIVE})
            for a in claimed])
        for actor in claimed:
            await self._publish_event("actor", actor.public_view())
        for actor in lost:
            await self._handle_actor_failure(actor, LOST_DURING_HEAD_OUTAGE)

    # --------------------------------------------------- durable mutations
    async def _durable(self, op: str, data: Dict) -> None:
        """Make one mutation durable BEFORE the caller acks it.

        WAL mode: group-commit append — resolves after the record is
        fsynced (many concurrent mutations share one fsync). Snapshot
        mode (redis backend): the debounced full-state save, whose
        durability window the external store's own persistence covers.
        No store: no-op (pure in-memory head).
        """
        if self.wal is not None:
            _seq, fut = self.wal.append_nowait(op, data)
            self._maybe_compact()
            await fut
        elif self.store is not None:
            self._schedule_save()

    async def _durable_batch(self, ops: List[Tuple[str, Dict]]) -> None:
        """`_durable` for many mutations at once: append every record
        BEFORE the first await so the whole batch resolves on one
        group-commit fsync instead of paying N serial commit windows."""
        if not ops:
            return
        if self.wal is not None:
            futs = [self.wal.append_nowait(op, data)[1] for op, data in ops]
            self._maybe_compact()
            await asyncio.gather(*futs)
        elif self.store is not None:
            self._schedule_save()

    def _maybe_compact(self) -> None:
        if self._compacting or self.wal is None or self.store is None:
            return
        if self.wal.size_bytes < int(CONFIG.gcs_wal_compact_bytes):
            return
        self._compacting = True
        self._hold_task(asyncio.get_running_loop().create_task(
            self._compact()))

    async def _compact(self) -> None:
        """Snapshot-and-truncate: save a full snapshot stamped with the
        latest WAL seq, then rotate the log keeping only records newer
        than the snapshot. A crash between the two steps is safe — replay
        skips records at or below the snapshot's seq."""
        try:
            async with self._save_lock:
                state = self._snapshot()
                await asyncio.to_thread(self._write_snapshot, state)
                await self.wal.rotate(int(state.get("seq", 0)))
        except Exception:
            logging.getLogger("ray_tpu").exception("WAL compaction failed")
        finally:
            self._compacting = False

    def _schedule_save(self) -> None:
        if self.store is None or self._save_pending:
            return
        self._save_pending = True
        loop = asyncio.get_running_loop()
        loop.call_later(
            CONFIG.head_save_debounce_s,
            lambda: self._hold_task(loop.create_task(
                self._save_state_async())))

    def _snapshot(self) -> Dict:
        """Shallow-copied state snapshot, built on the loop thread so the
        (possibly large) pickle+write can run off-loop without racing
        concurrent mutation."""
        return {
            "seq": self.wal.seq if self.wal is not None else 0,
            "head_incarnation": self.head_incarnation,
            "kv": {ns: dict(table) for ns, table in self.kv.items()},
            "jobs": {k: dict(v) for k, v in self.jobs.items()},
            "named_actors": [[list(k), v]
                             for k, v in self.named_actors.items()],
            "placement_groups": {k: dict(v)
                                 for k, v in self.placement_groups.items()},
            "pg_counter": self._pg_counter,
            "fenced_incarnations": dict(self.fenced_incarnations),
            "actors": [self._actor_record(a) for a in self.actors.values()],
            "nodes": [
                {"node_id": n.node_id, "incarnation": n.incarnation,
                 "addr": n.addr, "resources": n.resources.to_wire(),
                 "alive": True}
                for n in self.nodes.values() if n.alive
            ],
        }

    @staticmethod
    def _actor_record(a: ActorInfo) -> Dict:
        """Durable actor row — shared by snapshots and ``actor_create``
        WAL records so both restore through ``_restore_actor``."""
        return {"actor_id": a.actor_id, "spec_wire": a.spec_wire,
                "name": a.name, "namespace": a.namespace,
                "max_restarts": a.max_restarts,
                "state": a.state, "addr": a.addr, "node_id": a.node_id,
                "num_restarts": a.num_restarts, "owner_job": a.owner_job,
                "death_cause": a.death_cause, "pid": a.pid}

    async def _save_state_async(self) -> None:
        self._save_pending = False
        if self.store is None:
            return
        # serialize writers: a second debounced save during a slow write
        # must not race the same backend
        async with self._save_lock:
            state = self._snapshot()
            await asyncio.to_thread(self._write_snapshot, state)

    def _write_snapshot(self, state: Dict) -> None:
        import pickle

        self.store.save({name: pickle.dumps(value)
                         for name, value in state.items()})

    def _save_state(self) -> None:
        """Synchronous save (shutdown/teardown paths)."""
        if self.store is not None:
            self._write_snapshot(self._snapshot())

    def _hold_task(self, task: "asyncio.Task") -> "asyncio.Task":
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)
        return task

    # ------------------------------------- O(1) scheduler state (ISSUE 10)
    def _index_new_actor(self, info: ActorInfo) -> None:
        self._actor_state_counts[info.state] = \
            self._actor_state_counts.get(info.state, 0) + 1
        self._actors_by_job.setdefault(info.owner_job, set()).add(
            info.actor_id)
        if info.node_id and info.state != ACTOR_DEAD:
            self._actors_by_node.setdefault(info.node_id, set()).add(
                info.actor_id)

    def _actor_set_state(self, info: ActorInfo, state: str) -> None:
        """Single choke point for actor state transitions: keeps the
        per-state counts (metrics loop) and the node index exact without
        any table scan."""
        if state == info.state:
            return
        prev = self._actor_state_counts.get(info.state, 0) - 1
        if prev > 0:
            self._actor_state_counts[info.state] = prev
        else:
            self._actor_state_counts.pop(info.state, None)
        info.state = state
        self._actor_state_counts[state] = \
            self._actor_state_counts.get(state, 0) + 1
        if state == ACTOR_DEAD:
            self._uncommit_placement(info.actor_id)
            if info.node_id:
                bucket = self._actors_by_node.get(info.node_id)
                if bucket is not None:
                    bucket.discard(info.actor_id)
                    if not bucket:
                        self._actors_by_node.pop(info.node_id, None)
            self._prune_dead_actors()

    def _prune_dead_actors(self) -> None:
        """Dead-actor cache cap (raylint R10): keep the most recent
        ``_DEAD_ACTOR_CACHE`` DEAD actors for GetActor post-mortems and
        evict the rest — an actor-churning job (the actor_scale bench
        creates thousands) must not grow the head's table with every
        actor that ever lived. O(n) scan only on the death that crosses
        the cap."""
        if self._actor_state_counts.get(ACTOR_DEAD, 0) <= _DEAD_ACTOR_CACHE:
            return
        dead = [a for a in self.actors.values() if a.state == ACTOR_DEAD]
        # timeline[-1][0] is the death note's timestamp: evict oldest
        dead.sort(key=lambda a: a.timeline[-1][0] if a.timeline else 0.0)
        for victim in dead[:len(dead) - _DEAD_ACTOR_CACHE]:
            self.actors.pop(victim.actor_id, None)
            n = self._actor_state_counts.get(ACTOR_DEAD, 0) - 1
            if n > 0:
                self._actor_state_counts[ACTOR_DEAD] = n
            else:
                self._actor_state_counts.pop(ACTOR_DEAD, None)
            if victim.name and self.named_actors.get(
                    (victim.namespace, victim.name)) == victim.actor_id:
                self.named_actors.pop((victim.namespace, victim.name), None)
            bucket = self._actors_by_job.get(victim.owner_job)
            if bucket is not None:
                bucket.discard(victim.actor_id)
                if not bucket:
                    self._actors_by_job.pop(victim.owner_job, None)

    def _actor_set_node(self, info: ActorInfo, node_id: Optional[str]) -> None:
        if node_id == info.node_id:
            return
        if info.node_id:
            bucket = self._actors_by_node.get(info.node_id)
            if bucket is not None:
                bucket.discard(info.actor_id)
                if not bucket:
                    self._actors_by_node.pop(info.node_id, None)
        info.node_id = node_id
        if node_id and info.state != ACTOR_DEAD:
            self._actors_by_node.setdefault(node_id, set()).add(
                info.actor_id)

    def _rebuild_actor_indexes(self) -> None:
        """Recompute the derived actor indexes from the actor table —
        load-time only (snapshot restore + WAL replay mutate ActorInfo
        fields directly); every runtime transition goes through the
        incremental helpers."""
        self._actors_by_node = {}
        self._actors_by_job = {}
        self._actor_state_counts = {}
        for info in self.actors.values():
            self._index_new_actor(info)

    @property
    def COMMIT_WINDOW_S(self) -> float:
        # once the target agent's next resource report lands (~one gossip
        # period) its advertised availability already reflects the
        # placement; only younger commitments must be double-counted
        return max(1.5, 3 * CONFIG.gossip_period_ms / 1000.0)

    def _commit_placement(self, info: ActorInfo, request: ResourceSet,
                          node_id: str) -> None:
        self._uncommit_placement(info.actor_id)
        entries = self._committed_nodes.setdefault(node_id, {})
        entries[info.actor_id] = (time.monotonic(), request)
        agg = self._committed_agg.get(node_id)
        if agg is None:
            agg = self._committed_agg[node_id] = ResourceSet({})
        agg.add(request)
        self._committed_node_of[info.actor_id] = node_id

    def _uncommit_placement(self, actor_id: str) -> None:
        node_id = self._committed_node_of.pop(actor_id, None)
        if node_id is None:
            return
        entries = self._committed_nodes.get(node_id)
        if entries is None:
            return
        entry = entries.pop(actor_id, None)
        if entry is not None:
            if entries:
                self._committed_agg[node_id].subtract(
                    entry[1], allow_negative=True)
            else:
                # empty ledger: drop the aggregate instead of subtracting
                # down — float drift from add/subtract churn self-heals
                self._committed_nodes.pop(node_id, None)
                self._committed_agg.pop(node_id, None)

    def _prune_committed(self, node_id: str) -> None:
        """Age out commitments older than the gossip window. Entries are
        insertion-ordered (placements happen in time order), so this pops
        from the front — amortized O(1) per placement."""
        entries = self._committed_nodes.get(node_id)
        if not entries:
            return
        horizon = time.monotonic() - self.COMMIT_WINDOW_S
        for actor_id in list(entries):
            if entries[actor_id][0] >= horizon:
                break
            self._uncommit_placement(actor_id)

    def _effective_available(self, node: NodeInfo) -> ResourceSet:
        self._prune_committed(node.node_id)
        avail = node.resources.available.copy()
        pending = self._committed_agg.get(node.node_id)
        if pending is not None:
            avail.subtract(pending, allow_negative=True)
        return avail

    def _rank_update(self, node: NodeInfo) -> None:
        """Re-rank one node after a delta (register, resource report,
        death, recovery transition)."""
        if node.alive and not node.recovering:
            self._node_rank.update(node.node_id,
                                   node.resources.utilization())
        else:
            self._node_rank.remove(node.node_id)

    # ------------------------------------------------------------------ boot
    async def start(self) -> int:
        self.port = await self.server.start_tcp("0.0.0.0", self.port)
        self.server.set_disconnect_handler(self._on_disconnect)
        loop = asyncio.get_running_loop()
        if self.wal is not None:
            self.wal.start()
            # durable boot marker: a double restart with no snapshot in
            # between must still advance the head incarnation
            self._hold_task(loop.create_task(self.wal.append(
                "head_boot", {"incarnation": self.head_incarnation})))
        if self.recovering_nodes or self.recovering_actors \
                or self.recovering_jobs:
            self._hold_task(loop.create_task(self._recovery_reconcile()))
        for info in self.actors.values():
            # restored mid-scheduling: snapshots can't persist the retry
            # task, so re-arm it (agents re-register within the window)
            if info.state in (ACTOR_PENDING, ACTOR_RESTARTING):
                self._hold_task(loop.create_task(self._retry_schedule(info)))
        for pg_id, pg in list(self.placement_groups.items()):
            # same story for placement groups restored mid-placement: the
            # retry task is in-process state a snapshot can't persist
            if pg.get("state") == "PENDING":
                self._hold_task(loop.create_task(self._retry_place_pg(pg_id)))
        for name, factory in (
                ("health_check", self._health_check_loop),
                ("broadcast", self._broadcast_loop),
                ("metrics", self._metrics_loop)):
            self._hold_task(loop.create_task(self._supervise(name, factory)))
        await self._start_metrics_http()
        return self.port

    # ------------------------------------------------ Prometheus scrape (14)
    async def _start_metrics_http(self) -> None:
        """Minimal asyncio HTTP endpoint serving GET /metrics in
        Prometheus exposition format (``metrics_export_port``, 0 =
        disabled) — the head already aggregates every process's snapshot
        in the ``_metrics`` KV namespace, so scraping is a read + render,
        no extra agent. The bound port lands in <session>/metrics_port
        for the CLI (`ray_tpu metrics --scrape`) and tests."""
        self.metrics_port = 0
        self._metrics_http = None
        port = int(CONFIG.metrics_export_port)
        if port <= 0:
            return
        try:
            self._metrics_http = await asyncio.start_server(
                self._handle_metrics_http, host="0.0.0.0", port=port)
            self.metrics_port = \
                self._metrics_http.sockets[0].getsockname()[1]
            with open(os.path.join(self.session_dir, "metrics_port"),
                      "w") as f:
                f.write(str(self.metrics_port))
        except Exception:
            logging.getLogger("ray_tpu").exception(
                "metrics scrape endpoint failed to bind port %d", port)

    async def _handle_metrics_http(self, reader, writer) -> None:
        try:
            try:
                req = await asyncio.wait_for(reader.readline(), timeout=5)
                # drain request headers, bounded: the per-line timeout
                # alone lets a drip-feed client pin this coroutine forever
                for _ in range(100):
                    line = await asyncio.wait_for(reader.readline(),
                                                  timeout=5)
                    if line in (b"\r\n", b"\n", b""):
                        break
                else:
                    return  # >100 header lines: not a scraper, drop it
            except (asyncio.TimeoutError, ConnectionError):
                return
            parts = req.split()
            path = parts[1] if len(parts) > 1 else b"/"
            if parts and parts[0] != b"GET":
                status, body = b"405 Method Not Allowed", b"GET only\n"
            elif path.split(b"?")[0] in (b"/metrics", b"/"):
                status = b"200 OK"
                body = self._render_prometheus().encode()
            else:
                status, body = b"404 Not Found", b"try /metrics\n"
            writer.write(
                b"HTTP/1.1 " + status + b"\r\n"
                b"Content-Type: text/plain; version=0.0.4; "
                b"charset=utf-8\r\n"
                b"Content-Length: " + str(len(body)).encode() + b"\r\n"
                b"Connection: close\r\n\r\n" + body)
            await writer.drain()
        except Exception:
            pass  # a malformed scrape must never hurt the head
        finally:
            try:
                writer.close()
            except Exception:
                pass

    def _render_prometheus(self) -> str:
        from ray_tpu.util.metrics import render_prometheus

        snaps: List[Dict] = []
        for raw in (self.kv.get("_metrics") or {}).values():
            try:
                snaps.extend(json.loads(raw))
            except Exception:
                continue
        return render_prometheus(snaps)

    async def _supervise(self, name: str, factory) -> None:
        """Restart-on-crash supervisor for the head's background loops. A
        bare create_task'd loop that raises (one bad node record, one
        psutil hiccup) would otherwise silently stop health checking /
        gossip FOREVER — the cluster keeps accepting work while dead
        nodes stay 'alive'. Crashes are logged, counted
        (ray_tpu_gcs_loop_restarts), and restarted with a short backoff
        so a deterministic crash can't spin the head at 100% CPU."""
        import logging

        delay = 0.1
        while True:
            try:
                await factory()
                return  # a loop that RETURNS chose to stop; respect it
            except asyncio.CancelledError:
                raise
            except Exception:
                self.loop_restarts[name] = self.loop_restarts.get(name, 0) + 1
                logging.getLogger("ray_tpu").exception(
                    "head background loop %r crashed (restart #%d)",
                    name, self.loop_restarts[name])
                from ray_tpu._private.event import report_event

                try:
                    report_event("ERROR", "GCS_LOOP_CRASH",
                                 f"head loop {name} crashed; restarting",
                                 loop=name,
                                 restarts=self.loop_restarts[name])
                except Exception:
                    pass
                await asyncio.sleep(delay)
                delay = min(delay * 2, 5.0)

    def _register_routes(self) -> None:
        r = self.server.add_handler
        r("RegisterNode", self._register_node)
        r("UpdateResources", self._update_resources)
        r("GetReportStats", self._get_report_stats)
        r("GetClusterView", self._get_cluster_view)
        r("RegisterDriver", self._register_driver)
        r("KvPut", self._kv_put)
        r("KvGet", self._kv_get)
        r("KvDel", self._kv_del)
        r("KvKeys", self._kv_keys)
        r("KvExists", self._kv_exists)
        r("CreateActor", self._create_actor)
        r("CreateActorBatch", self._create_actor_batch)
        r("ActorReady", self._actor_ready)
        r("ActorReadyBatch", self._actor_ready_batch)
        r("ActorDied", self._actor_died)
        r("GetActor", self._get_actor)
        r("GetNamedActor", self._get_named_actor)
        r("ListActors", self._list_actors)
        r("KillActor", self._kill_actor)
        r("ListNodes", self._list_nodes)
        r("ObjectSummary", self._object_summary)
        r("Subscribe", self._subscribe)
        r("Publish", self._publish)
        r("CreatePlacementGroup", self._create_placement_group)
        r("RemovePlacementGroup", self._remove_placement_group)
        r("GetPlacementGroup", self._get_placement_group)
        r("ListPlacementGroups", self._list_placement_groups)
        r("ReportTaskEvents", self._report_task_events)
        r("ListTaskEvents", self._list_task_events)
        r("ListSpans", self._list_spans)
        r("GetEventStats", self._get_event_stats)
        r("RegisterJob", self._register_job)
        r("ListJobs", self._list_jobs)
        r("DrainNode", self._drain_node)
        r("GetHeadStatus", self._get_head_status)
        r("BcastJoin", self._bcast_join)
        r("BcastReady", self._bcast_ready)
        r("BcastReparent", self._bcast_reparent)
        r("BcastStats", self._bcast_stats)
        r("Ping", self._ping)

    async def _ping(self, conn, p) -> Dict:
        return {"ok": True}

    async def _get_head_status(self, conn, p) -> Dict:
        """Operator view of the head plane (CLI ``status``): incarnation,
        uptime, WAL health, and the last recovery's reconciliation."""
        return {
            "incarnation": self.head_incarnation,
            "started_at": self.started_at,
            "uptime_s": round(time.time() - self.started_at, 3),
            "persist": self.persist_path or "",
            "wal": self.wal.stats() if self.wal is not None else None,
            "last_recovery": dict(self.last_recovery),
            "recovering": {
                "nodes": len(self.recovering_nodes),
                "actors": len(self.recovering_actors),
                "jobs": len(self.recovering_jobs),
            },
        }

    # ------------------------------------------------------ node membership
    async def _register_node(self, conn: Connection, p: Dict) -> Dict:
        node_id = p["node_id"]
        incarnation = int(p.get("incarnation", 0))
        # fencing: this incarnation was declared dead (its actors were
        # failed over, its leases voided). Letting it back in after the
        # partition heals would resurrect zombie state — reject, and the
        # agent self-terminates on seeing the verdict.
        if incarnation <= self.fenced_incarnations.get(node_id, -1):
            from ray_tpu._private.event import report_event

            report_event("WARNING", "NODE_FENCED",
                         f"rejected re-register of fenced node "
                         f"{node_id[:12]} (incarnation {incarnation})",
                         node_id=node_id, incarnation=incarnation)
            return {"fenced": True, "node_id": node_id,
                    "incarnation": incarnation,
                    "fenced_incarnation":
                        self.fenced_incarnations.get(node_id, -1)}
        existing = self.nodes.get(node_id)
        if existing is not None and existing.alive:
            if existing.incarnation == incarnation:
                # same boot reconnecting (head restart / TCP blip inside
                # the grace window): adopt the new connection in place —
                # the node never died, so no removed/added events fire
                existing.conn = conn
                existing.addr = p["addr"]
                existing.resources = NodeResources.from_wire(p["resources"])
                existing.labels = existing.resources.labels
                existing.last_heartbeat = time.monotonic()
                existing.disconnected_at = None
                conn.meta["node_id"] = node_id
                conn.meta["role"] = "agent"
                self._rank_update(existing)
                if existing.recovering:
                    # restored-from-durable-store node claimed: reconcile
                    # its actors against the agent's ACTUAL live set
                    await self._claim_node(existing, p.get("actors"))
                await self._durable("node_register", {
                    "node_id": node_id, "incarnation": incarnation,
                    "addr": p["addr"], "resources": p["resources"],
                    "alive": True})
                return {"cluster_config": self.cluster_config,
                        "cluster_view": self._cluster_view()}
            # a NEWER boot superseding a still-"alive" record (the old
            # agent crashed; its grace window hasn't expired): the old
            # incarnation must die properly — fail its actors over and
            # fence it — or they'd sit ALIVE with a stale addr forever
            await self._mark_node_dead(
                existing, f"superseded by incarnation {incarnation}")
        info = NodeInfo(node_id, p["addr"],
                        NodeResources.from_wire(p["resources"]), conn,
                        incarnation=incarnation)
        self.nodes[node_id] = info
        conn.meta["node_id"] = node_id
        conn.meta["role"] = "agent"
        self._rank_update(info)
        # durable BEFORE the ack: an acked membership must survive kill -9
        await self._durable("node_register", {
            "node_id": node_id, "incarnation": incarnation,
            "addr": p["addr"], "resources": p["resources"], "alive": True})
        await self._publish_event("node", {"event": "added", "node_id": node_id,
                                           "addr": p["addr"],
                                           "incarnation": incarnation})
        return {"cluster_config": self.cluster_config,
                "cluster_view": self._cluster_view()}

    async def _register_driver(self, conn: Connection, p: Dict) -> Dict:
        conn.meta["role"] = "driver"
        job_id = p.get("job_id")
        conn.meta["job_id"] = job_id
        # re-registration (driver watchdog after a head restart / link
        # blip): move actor ownership onto the new connection so the old
        # connection's disconnect can't reap them
        old_conn = self._driver_conns.get(job_id)
        for actor_id in self._actors_by_job.get(job_id, ()):
            actor = self.actors.get(actor_id)
            if actor is None:
                continue
            if actor.owner_conn is old_conn and old_conn is not None \
                    and old_conn is not conn:
                actor.owner_conn = conn
            elif actor.owner_conn is None and actor.owner_job and \
                    actor.owner_job == job_id:
                # restored from a snapshot: re-adopt so driver-exit
                # cleanup reaches these actors again
                actor.owner_conn = conn
        self._driver_conns[job_id] = conn
        # a re-registering driver claims its restored job: the recovery
        # window must not declare it lost and reap its actors (jobs are
        # keyed `job_id or ""`, so normalize the same way)
        self.recovering_jobs.discard(job_id or "")
        existing = self.jobs.get(job_id or "")
        if existing is not None and existing.get("state") == "RUNNING":
            pass  # keep original start_time on re-register
        else:
            self.jobs[job_id or ""] = {
                "job_id": job_id, "start_time": time.time(),
                "state": "RUNNING", "entrypoint": p.get("entrypoint", ""),
            }
        await self._durable("job", {"key": job_id or "",
                                    "job": dict(self.jobs[job_id or ""])})
        return {"cluster_config": self.cluster_config,
                "cluster_view": self._cluster_view()}

    async def _update_resources(self, conn: Connection, p: Dict) -> Dict:
        node = self.nodes.get(p["node_id"])
        if node is None:
            return {}
        node.last_heartbeat = time.monotonic()
        if p.get("hb"):
            # unchanged-view heartbeat (versioned delta gossip): liveness
            # only — but if the heartbeat's snapshot version is not the
            # one we last applied, our view is stale (head restarted, or
            # a full report was lost) and the agent must resend in full
            self.report_stats["heartbeats"] = \
                self.report_stats.get("heartbeats", 0) + 1
            if p.get("v", 0) != node.resource_version:
                return {"resync": True}
            return {}
        self.report_stats["full_reports"] = \
            self.report_stats.get("full_reports", 0) + 1
        node.resources = NodeResources.from_wire(p["resources"])
        node.pending_demand = p.get("pending", [])
        node.resource_version = p.get("v", 0)
        self._rank_update(node)
        return {}

    async def _get_report_stats(self, conn: Connection, p) -> Dict:
        return dict(self.report_stats)

    def _cluster_view(self) -> Dict:
        return {
            nid: {"addr": n.addr, "resources": n.resources.to_wire(),
                  "alive": n.alive, "pending": n.pending_demand}
            for nid, n in self.nodes.items() if n.alive
        }

    async def _get_cluster_view(self, conn: Connection, p) -> Dict:
        return self._cluster_view()

    async def _list_nodes(self, conn: Connection, p) -> List[Dict]:
        return [
            {"node_id": nid, "addr": n.addr, "alive": n.alive,
             "resources_total": n.resources.total.to_wire(),
             "resources_available": n.resources.available.to_wire(),
             "labels": n.labels}
            for nid, n in self.nodes.items()
        ]

    async def _drain_node(self, conn: Connection, p: Dict) -> Dict:
        node = self.nodes.get(p["node_id"])
        if node and node.alive:
            await node.conn.push("Drain", {})
        return {"ok": True}

    # --------------------------------- object ownership ledger (ISSUE 15)
    async def _gather_object_refs(self, limit: int) -> Dict[str, Dict]:
        """Fan GetObjectRefs out to every alive agent. Per-request
        clients (this is a debugger surface, not a hot path); a node
        that fails to answer contributes an error entry, never a hang."""
        from ray_tpu._private.protocol import AsyncRpcClient

        alive = [(nid, n.addr) for nid, n in self.nodes.items()
                 if n.alive and n.addr and n.addr.get("port")]

        async def one(node_id: str, addr: Dict) -> Tuple[str, Dict]:
            client = AsyncRpcClient()
            try:
                await client.connect_tcp(addr["host"], addr["port"])
                reply = await client.call(
                    "GetObjectRefs", {"limit": limit},
                    timeout=CONFIG.object_introspect_timeout_s)
                return node_id, reply
            except Exception as e:
                return node_id, {"error": f"{type(e).__name__}: {e}"}
            finally:
                try:
                    await client.aclose()
                except Exception:
                    pass

        return dict(await asyncio.gather(
            *(one(nid, addr) for nid, addr in alive)))

    async def _object_summary(self, conn: Connection, p) -> Dict:
        """Cluster-wide object rollup: store bytes + ref tables of every
        process on every node, grouped by node / callsite / creator /
        tier (``ray_tpu memory``, util.state list/summarize_objects)."""
        p = p or {}
        group_by = p.get("group_by") or "node"
        limit = int(p.get("limit", 10000))
        nodes = await self._gather_object_refs(limit)

        # join key: object hex -> (node, tier, pinned) from store entries
        residency: Dict[str, Dict] = {}
        for node_id, nd in nodes.items():
            for row in nd.get("objects") or []:
                residency.setdefault(row["object_id"], {
                    "node_id": node_id, "tier": row.get("tier", ""),
                    "pinned": bool(row.get("pinned")),
                    "store_size": row.get("size_bytes", 0),
                    "creator_task": row.get("creator_task", "")})

        rows: List[Dict] = []
        for node_id, nd in nodes.items():
            for proc in nd.get("processes") or []:
                for o in proc.get("owned") or []:
                    res = residency.get(o["object_id"], {})
                    rows.append({
                        **o,
                        "owner_node_id": node_id,
                        "owner_pid": proc.get("pid", 0),
                        "owner_worker_id": proc.get("worker_id", ""),
                        "node_id": res.get("node_id", node_id),
                        "tier": res.get("tier",
                                        "inline" if o["state"] == "inline"
                                        else ""),
                        "pinned": res.get("pinned", False),
                    })

        def lineage_rollup(nd: Dict) -> Dict[str, int]:
            # each process dump carries its owner-side LineageLedger
            # summary (ISSUE 17); the node view is the sum
            lin = {"records": 0, "bytes": 0, "reconstructions": 0,
                   "evictions": 0}
            for proc in nd.get("processes") or []:
                for k, v in (proc.get("lineage") or {}).items():
                    lin[k] = lin.get(k, 0) + int(v or 0)
            return lin

        out: Dict[str, Any] = {
            "nodes": {
                node_id: {
                    "store": nd.get("store") or {},
                    "tiers": nd.get("tiers") or {},
                    "leak_suspects": nd.get("leak_suspects") or [],
                    "leak_scans": nd.get("leak_scans", 0),
                    "leak_repairs": nd.get("leak_repairs", 0),
                    "lineage": lineage_rollup(nd),
                    "num_processes": len(nd.get("processes") or []),
                    "error": nd.get("error"),
                }
                for node_id, nd in nodes.items()
            },
        }
        # per-COPY attribution: every sealed byte on every node counts
        # once per copy (a shard pulled to three reducers is three
        # copies of store usage), and a copy is attributed when its
        # object traces to an owner row or a recorded creating task —
        # the "≥95% of used store bytes attributable" acceptance stat
        owned_ids = {r["object_id"] for r in rows}
        store_bytes = attributed_bytes = 0
        for node_id, nd in nodes.items():
            for row in nd.get("objects") or []:
                if row.get("tier") == "remote":
                    continue  # no local bytes: the copy lives elsewhere
                sz = int(row.get("size_bytes") or 0)
                store_bytes += sz
                if row["object_id"] in owned_ids or row.get("creator_task") \
                        or row.get("creator_callsite"):
                    attributed_bytes += sz
        out["attribution"] = {
            "store_bytes": store_bytes,
            "attributed_bytes": attributed_bytes,
            "ratio": (attributed_bytes / store_bytes) if store_bytes else 1.0,
        }
        if p.get("detail"):
            out["rows"] = rows[:limit]
        if group_by == "tier":
            groups: Dict[str, Dict] = {}
            for node_id, nd in nodes.items():
                for row in nd.get("objects") or []:
                    g = groups.setdefault(row.get("tier") or "?", {
                        "count": 0, "total_bytes": 0})
                    g["count"] += 1
                    g["total_bytes"] += int(row.get("size_bytes") or 0)
        elif group_by == "node":
            groups = {}
            for node_id, nd in nodes.items():
                store = nd.get("store") or {}
                counts: Dict[str, int] = {}
                for proc in nd.get("processes") or []:
                    for k, v in (proc.get("counts") or {}).items():
                        counts[k] = counts.get(k, 0) + v
                groups[node_id] = {
                    "count": int(store.get("num_objects") or 0),
                    "total_bytes": int(store.get("used") or 0),
                    "refs": counts,
                    "leak_suspects": len(nd.get("leak_suspects") or []),
                }
        else:  # callsite | creator — owner-side provenance grouping
            key = "callsite" if group_by == "callsite" else "creator"
            groups = {}
            for row in rows:
                g = groups.setdefault(row.get(key) or "<unknown>", {
                    "count": 0, "total_bytes": 0, "borrowers": 0,
                    "task_pins": 0, "local_refs": 0, "pinned": 0,
                    "lineage": 0})
                g["count"] += 1
                g["total_bytes"] += int(row.get("size_bytes") or 0)
                g["borrowers"] += int(row.get("borrowers") or 0)
                g["task_pins"] += int(row.get("task_pins") or 0)
                g["local_refs"] += int(row.get("local_refs") or 0)
                g["pinned"] += 1 if row.get("pinned") else 0
                # objects a lost copy of which the owner can rebuild by
                # task replay (lineage record retained, ISSUE 17)
                g["lineage"] += 1 if row.get("lineage") else 0
        out["group_by"] = group_by
        out["groups"] = groups
        return out

    # ------------------------------------------- broadcast trees (ISSUE 9)
    async def _bcast_join(self, conn: Connection, p: Dict) -> Dict:
        return self.bcast.join(p["object_id"], p.get("size", 0),
                               p["addr"], p.get("roots") or [])

    async def _bcast_ready(self, conn: Connection, p: Dict) -> Dict:
        return self.bcast.ready(p["object_id"], p["addr"])

    async def _bcast_reparent(self, conn: Connection, p: Dict) -> Dict:
        return self.bcast.reparent(p["object_id"], p["addr"], p["dead"])

    async def _bcast_stats(self, conn: Connection, p) -> Dict:
        return self.bcast.stats((p or {}).get("object_id"))

    async def _health_check_loop(self) -> None:
        period = CONFIG.health_check_period_ms / 1000
        threshold = CONFIG.health_check_failure_threshold
        while True:
            await asyncio.sleep(period)
            now = time.monotonic()
            for node in list(self.nodes.values()):
                if node.recovering:
                    continue  # the recovery claim window owns its verdict
                if node.alive and now - node.last_heartbeat > period * threshold:
                    await self._mark_node_dead(node, "health check timeout")

    async def _mark_node_dead(self, node: NodeInfo, reason: str) -> None:
        if not node.alive:
            return
        node.alive = False
        node.recovering = False
        self.recovering_nodes.discard(node.node_id)
        self._rank_update(node)
        # in-flight placement commitments to a dead node are moot
        for actor_id in list(self._committed_nodes.get(node.node_id, ())):
            self._uncommit_placement(actor_id)
        # fence THIS incarnation: a later re-register from it (the
        # partition healed) is rejected; a fresh boot (higher
        # incarnation) may rejoin under the same node_id
        self.fenced_incarnations[node.node_id] = max(
            self.fenced_incarnations.get(node.node_id, -1),
            node.incarnation)
        from ray_tpu._private.event import report_event

        report_event("ERROR", "NODE_DEAD",
                     f"node {node.node_id[:12]} marked dead: {reason}",
                     node_id=node.node_id, reason=reason)
        # the death verdict (and its fence) must survive a head restart:
        # a fenced incarnation resurrecting through a stale snapshot would
        # be exactly the zombie state fencing exists to prevent
        await self._durable("node_dead", {
            "node_id": node.node_id, "incarnation": node.incarnation,
            "reason": reason})
        # drop the node's published system metrics: a dead node's last
        # cpu/mem/TPU gauges must not keep exporting as current
        metrics_ns = self.kv.get("_metrics")
        if metrics_ns:
            prefix = f"metrics::{node.node_id}".encode()
            for key in [k for k in metrics_ns if bytes(k).startswith(prefix)]:
                metrics_ns.pop(key, None)
        # drop the node out of every broadcast tree NOW: joiners stop
        # being routed to it and its children re-parent to a live
        # ancestor instead of waiting out relay-chunk timeouts
        try:
            self.bcast.on_node_removed(node.addr)
        except Exception:
            pass
        removed_msg = {"event": "removed", "node_id": node.node_id,
                       "reason": reason, "incarnation": node.incarnation,
                       "addr": node.addr, "time": time.time()}
        await self._publish_event("node", removed_msg)
        # fail-fast fan-out to the surviving agents (they don't subscribe
        # to pubsub channels): each drops its cached channels to the dead
        # peer so in-flight pulls/leases fail NOW instead of waiting out
        # chunk/RPC deadlines on a black-holed socket
        for other in list(self.nodes.values()):
            if other.alive and other is not node:
                try:
                    await other.conn.push("NodeRemoved", removed_msg)
                except Exception:
                    pass
        # Every actor on that node dies with it — including RECOVERING
        # ones: once the node's death is known there is nothing left to
        # claim them, so failing over NOW beats waiting out the window.
        # Indexed by node: the cascade reads only the dead node's actors,
        # not the whole cluster's table.
        for actor_id in list(self._actors_by_node.get(node.node_id, ())):
            actor = self.actors.get(actor_id)
            if actor is not None and actor.state in (
                ACTOR_ALIVE, ACTOR_PENDING, ACTOR_RESTARTING,
                ACTOR_RECOVERING,
            ):
                actor.recovering = False
                self.recovering_actors.discard(actor.actor_id)
                actor.death_node_id = node.node_id
                actor.death_incarnation = node.incarnation
                actor.note(f"node {node.node_id[:12]} died: {reason}")
                await self._handle_actor_failure(actor, f"node died: {reason}")
        # dead-node cache cap: the table must bound to live + recent dead
        # (the fence map stays — fencing is a safety contract, and an int
        # per ever-seen node_id is noise next to a NodeInfo)
        dead = [n for n in self.nodes.values() if not n.alive]
        if len(dead) > _DEAD_NODE_CACHE:
            dead.sort(key=lambda n: n.last_heartbeat)
            for victim in dead[:len(dead) - _DEAD_NODE_CACHE]:
                self.nodes.pop(victim.node_id, None)
                self.event_node_stats.pop(victim.node_id, None)

    async def _metrics_loop(self) -> None:
        """Publish head-level system gauges into the same KV pipeline the
        agents' node stats ride (reference: src/ray/stats/metric_defs.cc
        gcs_* series — actor/node/PG/job counts from the control plane)."""
        import json as _json

        from ray_tpu._private.protocol import STATS as _rpc_stats
        from ray_tpu.util.metrics import make_gauge_snapshot as g

        period = max(CONFIG.metrics_report_interval_ms, 1000) / 1000
        while True:
            await asyncio.sleep(period)
            try:
                # maintained incrementally on every transition — no
                # per-tick scan of a 5,000-actor table
                actor_states = dict(self._actor_state_counts)
                snaps = [
                    g("ray_tpu_gcs_nodes_alive", "Registered alive nodes.",
                      sum(1 for n in self.nodes.values() if n.alive)),
                    g("ray_tpu_gcs_nodes_dead", "Nodes marked dead.",
                      sum(1 for n in self.nodes.values() if not n.alive)),
                    g("ray_tpu_gcs_placement_groups",
                      "Placement groups registered.",
                      len(self.placement_groups)),
                    g("ray_tpu_gcs_jobs", "Jobs tracked by the head.",
                      len(self.jobs)),
                    g("ray_tpu_gcs_kv_entries",
                      "Internal-KV entries across namespaces.",
                      sum(len(ns) for ns in self.kv.values())),
                    g("ray_tpu_gcs_task_events_buffered",
                      "Task state-transition events held in the ring.",
                      len(self.task_events)),
                    g("ray_tpu_gcs_spans_buffered",
                      "Flight-recorder spans held in the head ring.",
                      len(self.span_events)),
                    g("ray_tpu_gcs_spans_dropped_total",
                      "Spans evicted from the head ring (overflow).",
                      max(0, self.span_events_total
                          - len(self.span_events))),
                    g("ray_tpu_gcs_named_actors",
                      "Named actors registered.", len(self.named_actors)),
                    g("ray_tpu_gcs_driver_connections",
                      "Driver connections attached to the head.",
                      len(self._driver_conns)),
                    g("ray_tpu_gcs_pubsub_channels",
                      "Pubsub channels with at least one subscriber.",
                      sum(1 for s in self.subscribers.values() if s)),
                    g("ray_tpu_gcs_pubsub_subscriptions",
                      "Total (channel, subscriber) pairs.",
                      sum(len(s) for s in self.subscribers.values())),
                    g("ray_tpu_gcs_loop_restarts",
                      "Supervised head background-loop crash restarts.",
                      sum(self.loop_restarts.values())),
                    g("ray_tpu_gcs_nodes_fenced",
                      "Node incarnations fenced after death verdicts.",
                      len(self.fenced_incarnations)),
                    g("ray_tpu_rpc_frames_in_total",
                      "Control-plane frames received by the head.",
                      _rpc_stats["frames_in"]),
                    g("ray_tpu_rpc_frames_out_total",
                      "Control-plane frames sent by the head.",
                      _rpc_stats["frames_out"]),
                    g("ray_tpu_rpc_bytes_in_total",
                      "Control-plane bytes received by the head.",
                      _rpc_stats["bytes_in"]),
                    g("ray_tpu_rpc_bytes_out_total",
                      "Control-plane bytes sent by the head.",
                      _rpc_stats["bytes_out"]),
                ]
                for state, count in actor_states.items():
                    snaps.append(g(
                        "ray_tpu_gcs_actors",
                        "Actors registered, by lifecycle state.",
                        count, {"state": state}))
                ns = self.kv.setdefault("_metrics", {})
                ns[b"metrics::head::gcs"] = _json.dumps(snaps).encode()
                from ray_tpu._private.events import REC as _rec

                if _rec.enabled and _rec.counter != _rec.flushed:
                    # the head's own ring drains in-process — no RPC
                    for sp in _rec.drain():
                        self.span_events.append(
                            ("head", "head", os.getpid(), sp))
                        self.span_events_total += 1
            except Exception:
                pass  # metrics must never take the head down

    async def _broadcast_loop(self) -> None:
        """Gossip the cluster resource view to all agents (ray_syncer analog)."""
        period = max(CONFIG.gossip_period_ms, 50) / 1000
        while True:
            await asyncio.sleep(period)
            view = self._cluster_view()
            for node in list(self.nodes.values()):
                if node.alive:
                    await node.conn.push("ClusterView", view)

    async def _on_disconnect(self, conn: Connection) -> None:
        # identity checks: a watchdog reconnect replaces the registered
        # connection; the stale connection's disconnect must not kill the
        # freshly re-registered node/driver
        node_id = conn.meta.get("node_id")
        if node_id and node_id in self.nodes and \
                self.nodes[node_id].conn is conn:
            node = self.nodes[node_id]
            grace = float(CONFIG.node_disconnect_grace_s)
            if grace <= 0 or not node.alive:
                await self._mark_node_dead(node, "agent disconnected")
            elif node.disconnected_at is None:
                # reconnect grace: one lost TCP connection is not a dead
                # node — give the agent's watchdog a window to re-register
                # before its actors are failed over. The heartbeat budget
                # (health check loop) still bounds a SILENT node's
                # lifetime, so grace only shortens nothing and saves
                # healthy nodes from transient blips.
                node.disconnected_at = time.monotonic()
                self._hold_task(asyncio.get_running_loop().create_task(
                    self._disconnect_grace(node, conn, grace)))
        if conn.meta.get("role") == "driver":
            job_id = conn.meta.get("job_id")
            if self._driver_conns.get(job_id) is conn:
                self._driver_conns.pop(job_id, None)
                if job_id in self.jobs:
                    self.jobs[job_id]["state"] = "FINISHED"
                    await self._durable("job", {
                        "key": job_id, "job": dict(self.jobs[job_id])})
                # Non-detached actors owned by this driver die with it.
                for actor_id in list(self._actors_by_job.get(job_id, ())):
                    actor = self.actors.get(actor_id)
                    if actor is not None and actor.owner_conn is conn \
                            and not actor.detached \
                            and actor.state != ACTOR_DEAD:
                        await self._kill_actor_internal(
                            actor, "owner driver exited")
                # Non-detached placement groups die with their driver
                # too — leaked bundles would pin cluster resources until
                # head restart (reference: GcsPlacementGroupManager::
                # CleanPlacementGroupIfNeededWhenJobDead).
                if job_id:
                    for pg_id, pg in list(self.placement_groups.items()):
                        if pg.get("job_id") == job_id \
                                and pg.get("lifetime") != "detached":
                            await self._remove_pg_internal(pg_id)
        for subs in self.subscribers.values():
            subs.discard(conn)

    async def _disconnect_grace(self, node: NodeInfo, old_conn: Connection,
                                grace: float) -> None:
        await asyncio.sleep(grace)
        current = self.nodes.get(node.node_id)
        if current is not node or not node.alive:
            return  # replaced by a fresh boot, or already dead
        if node.conn is not old_conn or node.disconnected_at is None:
            return  # re-registered within the window
        await self._mark_node_dead(
            node, f"agent disconnected (no re-register within {grace:g}s "
                  "grace)")

    # ------------------------------------------------------------------- kv
    async def _kv_put(self, conn, p) -> bool:
        ns_name = p.get("ns", "default")
        ns = self.kv.setdefault(ns_name, {})
        key = p["key"]
        if p.get("overwrite", True) or key not in ns:
            ns[key] = p["value"]
            # "_metrics" churns every few seconds per process and is
            # rebuilt live after a restart — logging it would be pure WAL
            # noise between compactions
            if ns_name != "_metrics":
                await self._durable("kv_put", {
                    "ns": ns_name, "key": key, "value": p["value"],
                    "overwrite": True})
            return True
        return False

    async def _kv_get(self, conn, p):
        return self.kv.get(p.get("ns", "default"), {}).get(p["key"])

    async def _kv_del(self, conn, p) -> int:
        ns_name = p.get("ns", "default")
        ns = self.kv.get(ns_name, {})
        if p.get("prefix"):
            keys = [k for k in ns if k.startswith(p["key"])]
            for k in keys:
                del ns[k]
            if keys and ns_name != "_metrics":
                await self._durable("kv_del", {
                    "ns": ns_name, "key": p["key"], "prefix": True})
            return len(keys)
        n = 1 if ns.pop(p["key"], None) is not None else 0
        if n and ns_name != "_metrics":
            await self._durable("kv_del", {"ns": ns_name, "key": p["key"]})
        return n

    async def _kv_keys(self, conn, p) -> List[bytes]:
        ns = self.kv.get(p.get("ns", "default"), {})
        prefix = p.get("prefix", b"")
        return [k for k in ns if k.startswith(prefix)]

    async def _kv_exists(self, conn, p) -> bool:
        return p["key"] in self.kv.get(p.get("ns", "default"), {})

    # --------------------------------------------------------------- actors
    def _admit_actor(self, conn: Connection, p: Dict
                     ) -> Tuple[Optional[Dict], Optional[ActorInfo],
                                Optional[Tuple[str, Dict]]]:
        """Registry admission shared by single and batched creates:
        returns (terminal_reply, new_info, durable_op). Exactly one of
        terminal_reply / new_info is set; raises for a taken name."""
        spec = p["spec"]
        actor_id = p["actor_id"]
        name = p.get("name", "")
        namespace = p.get("namespace", "default")
        dup = self.actors.get(actor_id)
        if dup is not None:
            # duplicate delivery: the original ack died with the head and
            # the driver's outage-queued head_call retried a create the
            # WAL already made durable (actor ids are client-generated,
            # so same id == same logical create) — adopt, never
            # double-create or fail a create that actually succeeded
            if dup.owner_conn is None or dup.owner_conn.closed:
                dup.owner_conn = conn
            return {"actor_id": actor_id, "state": dup.state}, None, None
        if name:
            existing_id = self.named_actors.get((namespace, name))
            if existing_id:
                existing = self.actors.get(existing_id)
                if existing and existing.state != ACTOR_DEAD:
                    if p.get("get_if_exists"):
                        return {"existing": existing.public_view()}, \
                            None, None
                    raise ValueError(f"actor name '{name}' already taken")
        info = ActorInfo(actor_id, spec, name, namespace,
                         p.get("max_restarts", 0), conn)
        info.owner_job = conn.meta.get("job_id")
        self.actors[actor_id] = info
        self._index_new_actor(info)
        if name:
            self.named_actors[(namespace, name)] = actor_id
        return None, info, ("actor_create", self._actor_record(info))

    async def _create_actor(self, conn: Connection, p: Dict) -> Dict:
        reply, info, op = self._admit_actor(conn, p)
        if reply is not None:
            return reply
        # durable before scheduling (and before the ack): a kill -9 right
        # after this reply restores the actor PENDING and reschedules it
        await self._durable(*op)
        ok = await self._schedule_actor(info)
        if not ok:
            # No feasible node right now; keep PENDING and retry when nodes join
            self._hold_task(asyncio.get_running_loop().create_task(
                self._retry_schedule(info)))
        return {"actor_id": info.actor_id, "state": info.state}

    async def _create_actor_batch(self, conn: Connection, p: Dict) -> Dict:
        """Coalesced driver-side creates (ISSUE 10): one frame, one WAL
        group commit, and StartActor pushes grouped into ONE
        StartActorBatch frame per target node. Entries keep per-entry
        semantics — a taken name (or any admission error) fails only its
        entry, and the at-least-once dedupe-by-actor-id contract of the
        single path is identical."""
        results: List[Dict] = []
        admitted: List[ActorInfo] = []
        ops: List[Tuple[str, Dict]] = []
        for entry in p.get("items", ()):
            try:
                reply, info, op = self._admit_actor(conn, entry)
            except ValueError as e:
                results.append({"actor_id": entry.get("actor_id"),
                                "error": str(e)})
                continue
            if reply is not None:
                results.append(reply)
                continue
            admitted.append(info)
            ops.append(op)
            results.append({"actor_id": info.actor_id, "state": info.state})
        # one fsync window for the whole burst, before any entry is acked
        await self._durable_batch(ops)
        sink: List[Tuple[NodeInfo, ActorInfo, Dict]] = []
        for info in admitted:
            if not await self._schedule_actor(info, push_sink=sink):
                self._hold_task(asyncio.get_running_loop().create_task(
                    self._retry_schedule(info)))
        by_node: Dict[str, Tuple[NodeInfo, List[ActorInfo], List[Dict]]] = {}
        for node, info, payload in sink:
            entry = by_node.setdefault(node.node_id, (node, [], []))
            entry[1].append(info)
            entry[2].append(payload)
        for node, infos, payloads in by_node.values():
            try:
                if len(payloads) == 1:
                    await node.conn.push("StartActor", payloads[0])
                else:
                    await node.conn.push("StartActorBatch",
                                         {"items": payloads})
            except Exception:
                # lost frame: re-arm the normal retry machinery per actor
                for info in infos:
                    self._hold_task(asyncio.get_running_loop().create_task(
                        self._retry_schedule(info)))
        return {"results": results}

    async def _schedule_actor(self, info: ActorInfo,
                              push_sink: Optional[List] = None) -> bool:
        """Pick the least-utilized feasible node (GcsActorScheduler analog).

        O(1)-per-placement in the common case (ISSUE 10): candidates come
        from the utilization-ranked schedulable-node index — the walk
        stops at the first node whose committed-adjusted availability
        fits — and the anti-double-booking accounting reads the
        incrementally-maintained per-node committed ledger instead of
        scanning actors (reference: GcsActorScheduler tracks leased
        resources per node). Constrained placements (PG / affinity /
        labels) filter the same ranked order.

        With ``push_sink``, the chosen (node, info, payload) is appended
        instead of pushed — the batched create path groups one
        StartActorBatch frame per node."""
        request = ResourceSet.from_wire(info.spec_wire.get("resources", {}))
        strategy = info.spec_wire.get("scheduling_strategy")
        pg = info.spec_wire.get("pg")  # [pg_id, bundle_index] or None
        pg_node: Optional[str] = None
        if pg:
            group = self.placement_groups.get(pg[0])
            if not group or group["state"] == "REMOVED":
                await self._handle_actor_death(
                    info, f"placement group {pg[0]} removed")
                return True
            if group["state"] != "CREATED":
                return False  # PENDING: _retry_schedule polls us again
            if pg[1] is None or pg[1] < 0:
                # bundle_index -1 = any bundle: round-robin over the group's
                # nodes; the agent maps onto a concrete local bundle.
                rr = group.get("rr", 0)
                group["rr"] = rr + 1
                pg_node = group["placement"][rr % len(group["placement"])]
            else:
                pg_node = group["placement"][pg[1]]
        node: Optional[NodeInfo] = None
        if pg_node is not None or strategy:
            # constrained path: filter the ranked order (already ascending
            # by utilization, alive + claimed only)
            candidates = []
            for node_id in self._node_rank.ordered_ids():
                n = self.nodes.get(node_id)
                if n is None:
                    continue
                if pg_node is not None and n.node_id != pg_node:
                    continue
                if strategy and strategy.get("type") == "node_affinity":
                    if n.node_id != strategy.get("node_id"):
                        continue
                if strategy and strategy.get("type") == "node_label":
                    if not label_constraints_match(
                            n.labels, strategy.get("hard") or {}):
                        continue
                if pg_node is None and \
                        not request.feasible_on(n.resources.total):
                    continue
                candidates.append(n)
            if not candidates:
                return False
            fits = [n for n in candidates
                    if request.fits(self._effective_available(n))]
            pool = fits or candidates
            if strategy and strategy.get("type") == "node_label":
                soft = strategy.get("soft") or {}
                # stable sort: utilization rank order is preserved within
                # each soft-match group
                pool.sort(key=lambda n: not label_constraints_match(
                    n.labels, soft))
            node = pool[0]
        else:
            # default path: walk ascending utilization, first fit wins;
            # fall back to the least-utilized feasible node when nothing
            # fits right now (the agent queues the start until capacity
            # frees, exactly like the old sorted-pool pick)
            first_feasible: Optional[NodeInfo] = None
            for node_id in self._node_rank.ordered_ids():
                n = self.nodes.get(node_id)
                if n is None:
                    continue
                if not request.feasible_on(n.resources.total):
                    continue
                if request.fits(self._effective_available(n)):
                    node = n
                    break
                if first_feasible is None:
                    first_feasible = n
            if node is None:
                node = first_feasible
            if node is None:
                return False
        if node.conn.closed:
            # mid-grace-window: the agent's connection is down and push()
            # would silently no-op — the StartActor frame would be LOST
            # and the actor wedged PENDING with no retry task. Report
            # failure so _retry_schedule keeps polling until the agent
            # re-registers (or the grace expires and the node dies).
            return False
        self._actor_set_node(info, node.node_id)
        info.placed_at = time.monotonic()
        self._commit_placement(info, request, node.node_id)
        payload = {"spec": info.spec_wire, "actor_id": info.actor_id}
        if push_sink is not None:
            push_sink.append((node, info, payload))
            return True
        try:
            await node.conn.push("StartActor", payload)
        except Exception:
            return False
        return True

    async def _retry_schedule(self, info: ActorInfo) -> None:
        deadline = time.monotonic() + CONFIG.actor_creation_timeout_ms / 1000
        while time.monotonic() < deadline:
            await asyncio.sleep(1.0)
            if info.state != ACTOR_PENDING and info.state != ACTOR_RESTARTING:
                return
            if await self._schedule_actor(info):
                return
        if info.state in (ACTOR_PENDING, ACTOR_RESTARTING):
            await self._handle_actor_death(info, "no feasible node for actor resources")

    def _apply_actor_ready(self, info: ActorInfo, p: Dict,
                           conn_node: Optional[str]) -> Dict:
        """Shared readiness transition; returns the durable op payload so
        a batch commits every entry in ONE WAL group-commit window."""
        self._actor_set_state(info, ACTOR_ALIVE)
        self._uncommit_placement(info.actor_id)
        info.addr = p["addr"]
        info.pid = p.get("pid", 0)
        # legacy direct reports arrive on the WORKER's head connection (no
        # node_id in conn.meta); relayed batches carry the agent's node
        self._actor_set_node(
            info, conn_node or p.get("node_id") or info.node_id)
        # a worker's ready report also claims a RECOVERING actor (e.g.
        # the ready raced the head's death and is being re-delivered)
        info.recovering = False
        self.recovering_actors.discard(info.actor_id)
        info.note(f"alive on {(info.node_id or '?')[:12]}")
        return {"actor_id": info.actor_id, "state": ACTOR_ALIVE,
                "addr": info.addr, "pid": info.pid,
                "node_id": info.node_id}

    async def _actor_ready(self, conn: Connection, p: Dict) -> None:
        info = self.actors.get(p["actor_id"])
        if not info:
            return
        op = self._apply_actor_ready(info, p, conn.meta.get("node_id"))
        await self._durable("actor_update", op)
        await self._publish_event("actor", info.public_view())

    async def _actor_ready_batch(self, conn: Connection, p: Dict) -> Dict:
        """A node agent's coalesced worker readiness reports (ISSUE 10):
        every entry commits in one WAL group-commit window and the agent
        acks its workers only after this reply — per-entry at-least-once
        semantics are preserved through the relay."""
        conn_node = conn.meta.get("node_id") or p.get("node_id")
        ops = []
        ready: List[ActorInfo] = []
        for entry in p.get("items", ()):
            info = self.actors.get(entry["actor_id"])
            if not info:
                continue
            ops.append(("actor_update",
                        self._apply_actor_ready(info, entry, conn_node)))
            ready.append(info)
        await self._durable_batch(ops)
        for info in ready:
            await self._publish_event("actor", info.public_view())
        return {"n": len(ready)}

    async def _actor_died(self, conn: Connection, p: Dict) -> None:
        info = self.actors.get(p["actor_id"])
        if not info or info.state == ACTOR_DEAD:
            return
        await self._handle_actor_failure(info, p.get("reason", "worker died"))

    async def _handle_actor_failure(self, info: ActorInfo, reason: str) -> None:
        from ray_tpu._private.event import report_event

        report_event("WARNING", "ACTOR_FAILURE",
                     f"actor {info.actor_id[:12]} ({info.class_name}) "
                     f"failed: {reason}",
                     actor_id=info.actor_id, reason=reason,
                     restarts=info.num_restarts)
        if info.num_restarts < info.max_restarts or info.max_restarts == -1:
            info.num_restarts += 1
            self._actor_set_state(info, ACTOR_RESTARTING)
            self._uncommit_placement(info.actor_id)
            info.note(f"restarting (#{info.num_restarts}): {reason}")
            info.addr = None
            await self._durable("actor_update", {
                "actor_id": info.actor_id, "state": ACTOR_RESTARTING,
                "num_restarts": info.num_restarts, "addr": None})
            await self._publish_event("actor", info.public_view())
            if not await self._schedule_actor(info):
                self._hold_task(asyncio.get_running_loop().create_task(
                self._retry_schedule(info)))
        else:
            await self._handle_actor_death(info, reason)

    async def _handle_actor_death(self, info: ActorInfo, reason: str) -> None:
        self._actor_set_state(info, ACTOR_DEAD)
        info.death_cause = reason
        info.note(f"dead: {reason}")
        info.addr = None
        info.recovering = False
        self.recovering_actors.discard(info.actor_id)
        dropped_name = False
        if (info.namespace, info.name) in self.named_actors:
            if self.named_actors[(info.namespace, info.name)] == info.actor_id:
                del self.named_actors[(info.namespace, info.name)]
                dropped_name = True
        await self._durable("actor_update", {
            "actor_id": info.actor_id, "state": ACTOR_DEAD,
            "death_cause": reason, "addr": None,
            "max_restarts": info.max_restarts,
            "drop_name": dropped_name})
        await self._publish_event("actor", info.public_view())

    async def _get_actor(self, conn, p) -> Optional[Dict]:
        info = self.actors.get(p["actor_id"])
        return info.public_view() if info else None

    async def _get_named_actor(self, conn, p) -> Optional[Dict]:
        actor_id = self.named_actors.get((p.get("namespace", "default"), p["name"]))
        if actor_id is None:
            return None
        return self.actors[actor_id].public_view()

    async def _list_actors(self, conn, p) -> List[Dict]:
        return [a.public_view() for a in self.actors.values()]

    async def _kill_actor(self, conn, p) -> Dict:
        info = self.actors.get(p["actor_id"])
        if not info:
            return {"ok": False}
        if p.get("no_restart", True):
            info.max_restarts = info.num_restarts  # suppress further restarts
        await self._kill_actor_internal(info, "ray_tpu.kill")
        return {"ok": True}

    async def _kill_actor_internal(self, info: ActorInfo, reason: str) -> None:
        node = self.nodes.get(info.node_id) if info.node_id else None
        if node and node.alive:
            await node.conn.push("KillActorWorker", {"actor_id": info.actor_id})
        await self._handle_actor_death(info, reason)

    # --------------------------------------------------------------- pubsub
    async def _subscribe(self, conn: Connection, p) -> bool:
        for channel in p["channels"]:
            self.subscribers.setdefault(channel, set()).add(conn)
        return True

    async def _publish(self, conn: Connection, p) -> int:
        return await self._publish_event(p["channel"], p["message"])

    async def _publish_event(self, channel: str, message: Any) -> int:
        subs = self.subscribers.get(channel, set())
        n = 0
        for conn in list(subs):
            if conn.closed:
                subs.discard(conn)
                continue
            await conn.push("Pub", {"channel": channel, "message": message})
            n += 1
        return n

    # ------------------------------------------------------ placement groups
    async def _create_placement_group(self, conn: Connection, p: Dict) -> Dict:
        """Reserve bundles across nodes with the requested strategy.

        2-phase (prepare on agents, rollback on failure) like the reference's
        PG protocol (reference: node_manager.proto:385-392 Prepare/Commit).
        Infeasible groups stay PENDING and are retried as nodes/resources
        appear (reference: GcsPlacementGroupManager pending queue).
        """
        pg_id = p["pg_id"]
        self.placement_groups[pg_id] = {
            "pg_id": pg_id, "state": "PENDING", "bundles": p["bundles"],
            "strategy": p.get("strategy", "PACK"), "placement": None,
            "name": p.get("name", ""),
            # ownership: non-detached groups die with their creating
            # driver (reference: GcsPlacementGroupManager job-death
            # cleanup); "detached" lifetime opts out
            "lifetime": p.get("lifetime", ""),
            "job_id": conn.meta.get("job_id", ""),
        }
        await self._durable("pg", {"pg": dict(self.placement_groups[pg_id])})
        if await self._try_place_pg(pg_id):
            return {"state": "CREATED",
                    "placement": self.placement_groups[pg_id]["placement"]}
        self._hold_task(
            asyncio.get_running_loop().create_task(self._retry_place_pg(pg_id)))
        return {"state": "PENDING"}

    async def _try_place_pg(self, pg_id: str) -> bool:
        pg = self.placement_groups.get(pg_id)
        if pg is None or pg["state"] != "PENDING":
            return pg is not None and pg["state"] == "CREATED"
        bundles = [ResourceSet.from_wire(b) for b in pg["bundles"]]
        placement = self._place_bundles(bundles, pg["strategy"])
        if placement is None:
            return False
        prepared = []
        ok = True
        for idx, (bundle, node_id) in enumerate(zip(bundles, placement)):
            node = self.nodes[node_id]
            try:
                resp = await asyncio.wait_for(
                    self._agent_call(node, "PreparePGBundle",
                                     {"pg_id": pg_id, "bundle_index": idx,
                                      "resources": bundle.to_wire()}),
                    timeout=CONFIG.pg_prepare_timeout_s,
                )
                if resp and resp.get("ok"):
                    prepared.append((node, idx, bundle))
                else:
                    ok = False
                    break
            except Exception:
                # A timed-out prepare may still land on the agent; roll it
                # back too (ReturnPGBundle is idempotent) so the reservation
                # can't leak.
                prepared.append((node, idx, bundle))
                ok = False
                break
        # The group may have been removed while we awaited the prepares;
        # committing would resurrect it and leak the agents' reservations.
        if pg["state"] != "PENDING":
            ok = False
        if not ok:
            for node, idx, bundle in prepared:
                await node.conn.push("ReturnPGBundle",
                                     {"pg_id": pg_id, "bundle_index": idx})
            return False
        pg["state"] = "CREATED"
        pg["placement"] = placement
        await self._durable("pg", {"pg": dict(pg)})
        return True

    async def _retry_place_pg(self, pg_id: str) -> None:
        first = True
        while True:
            # fast first retry: a create racing its predecessor's bundle
            # return (concurrent handler dispatch) should land on the
            # next tick, not pay the full retry period
            await asyncio.sleep(0.05 if first
                                else CONFIG.pg_retry_place_period_s)
            first = False
            pg = self.placement_groups.get(pg_id)
            if pg is None or pg["state"] != "PENDING":
                return
            if await self._try_place_pg(pg_id):
                return

    def _place_bundles(self, bundles: List[ResourceSet], strategy: str
                       ) -> Optional[List[str]]:
        alive = [n for n in self.nodes.values()
                 if n.alive and not n.recovering]
        if not alive:
            return None
        placement: List[str] = []
        # Work on copies of availability so multi-bundle accounting is correct.
        avail = {n.node_id: n.resources.available.copy() for n in alive}
        if strategy in ("STRICT_PACK",):
            for n in alive:
                trial = avail[n.node_id].copy()
                if all(trial.subtract(b) for b in bundles):
                    return [n.node_id] * len(bundles)
            return None
        if strategy in ("STRICT_SPREAD",):
            used = set()
            for b in bundles:
                cand = [n for n in alive
                        if n.node_id not in used and b.fits(avail[n.node_id])]
                if not cand:
                    return None
                cand.sort(key=lambda n: n.resources.utilization())
                placement.append(cand[0].node_id)
                used.add(cand[0].node_id)
                avail[cand[0].node_id].subtract(b)
            return placement
        # PACK / SPREAD: best-effort
        prefer_pack = strategy == "PACK"
        for b in bundles:
            cand = [n for n in alive if b.fits(avail[n.node_id])]
            if not cand:
                return None
            if prefer_pack and placement:
                same = [n for n in cand if n.node_id == placement[-1]]
                if same:
                    cand = same
            elif not prefer_pack:
                cand.sort(key=lambda n: placement.count(n.node_id))
            placement.append(cand[0].node_id)
            avail[cand[0].node_id].subtract(b)
        return placement

    async def _agent_call(self, node: NodeInfo, method: str, payload: Dict):
        """Request/response to an agent over its persistent connection."""
        fut = asyncio.get_running_loop().create_future()
        key = f"__agent_reply__{id(fut)}"
        self.kv.setdefault("__internal__", {})

        # Use an ephemeral reply channel over pubsub semantics: the agent
        # replies by calling "Publish" on channel `key`.
        def cleanup(_):
            self.subscribers.pop(key, None)

        class _FutConn:
            closed = False

            async def push(self_inner, method_inner, p_inner):
                if not fut.done():
                    fut.set_result(p_inner["message"])

        self.subscribers[key] = {_FutConn()}
        fut.add_done_callback(cleanup)
        await node.conn.push(method, {**payload, "reply_channel": key})
        return await fut

    async def _remove_placement_group(self, conn, p) -> Dict:
        return {"ok": await self._remove_pg_internal(p["pg_id"])}

    async def _remove_pg_internal(self, pg_id: str) -> bool:
        """Tear a PG down: mark REMOVED, return its bundles, persist.
        Shared by the client RPC and driver-death cleanup."""
        pg = self.placement_groups.get(pg_id)
        if not pg or pg["state"] == "REMOVED":
            return False
        # mark REMOVED before any await: handlers dispatch concurrently,
        # so a Get/Create processed mid-removal must already see the
        # terminal state (and _try_place_pg's state check must abort)
        placement = pg.get("placement")
        pg["state"] = "REMOVED"
        if placement:
            for idx, node_id in enumerate(placement):
                node = self.nodes.get(node_id)
                if node and node.alive:
                    await node.conn.push("ReturnPGBundle",
                                         {"pg_id": pg_id, "bundle_index": idx})
        await self._durable("pg_remove", {"pg_id": pg_id})
        return True

    async def _get_placement_group(self, conn, p) -> Optional[Dict]:
        return self.placement_groups.get(p["pg_id"])

    async def _list_placement_groups(self, conn, p) -> List[Dict]:
        return list(self.placement_groups.values())

    # ----------------------------------------------------------- task events
    async def _report_task_events(self, conn, p) -> Dict:
        # v2: columnar tuples (task_id, job_id, name, state, type, time)
        # with node_id once per frame — dicts are built only on query.
        # Eviction is the deque's own maxlen (was an O(n) list copy per
        # overflow). The reply is the read-your-writes ack: a flush that
        # awaits it is guaranteed visible to the next ListTaskEvents.
        node_id = p.get("node_id", "")
        n_ev = 0
        for ev in p.get("events_v2", ()):
            self.task_events.append((node_id, ev))
            n_ev += 1
        for ev in p.get("events", ()):  # legacy dict form
            self.task_events.append((ev.get("node_id", node_id), ev))
            n_ev += 1
        spans = p.get("spans") or ()
        if spans or p.get("ring"):
            role, pid = p.get("role", ""), p.get("pid", 0)
            for sp in spans:
                self.span_events.append((node_id, role, pid, sp))
            self.span_events_total += len(spans)
            st = self.event_node_stats.setdefault(
                node_id, {"events": 0, "spans": 0, "flushes": 0,
                          "rings": {}})
            st["events"] += n_ev
            st["spans"] += len(spans)
            st["flushes"] += 1
            st["last_flush"] = time.time()
            ring = p.get("ring")
            if ring:
                st["rings"][f"{role}-{pid}"] = ring
        return {"ok": True, "events": n_ev, "spans": len(spans)}

    @staticmethod
    def _event_to_dict(node_id: str, ev) -> Dict:
        if isinstance(ev, dict):
            return ev
        task_id, job_id, name, state, task_type, t = ev
        return {
            "task_id": task_id.hex() if isinstance(task_id, bytes) else task_id,
            "job_id": job_id.hex() if isinstance(job_id, bytes) else job_id,
            "name": name, "state": state, "type": task_type, "time": t,
            "node_id": node_id,
        }

    async def _list_task_events(self, conn, p) -> List[Dict]:
        # filter + slice on the stored tuples, dict-render only the tail —
        # a full buffer is 100k entries and this runs on every poll
        limit = p.get("limit", 1000)
        job = p.get("job_id")
        if job:
            def match(ev):
                if isinstance(ev, dict):
                    return ev.get("job_id") == job
                jid = ev[1]
                return (jid.hex() if isinstance(jid, bytes) else jid) == job

            picked: List = []
            for nid, ev in reversed(self.task_events):
                if match(ev):
                    picked.append((nid, ev))
                    if len(picked) >= limit:
                        break
            picked.reverse()
        else:
            skip = max(0, len(self.task_events) - limit)
            picked = list(itertools.islice(self.task_events, skip, None))
        return [self._event_to_dict(nid, ev) for nid, ev in picked]

    async def _list_spans(self, conn, p) -> List[Dict]:
        """Flight-recorder spans, filterable by trace id or the task-hex
        prefix carried in span extras (``ray_tpu trace <task_id>``)."""
        from ray_tpu._private.events import _span_dict

        limit = p.get("limit", 20000)
        trace = p.get("trace")
        task = p.get("task")  # hex prefix match on extra["task"]
        out: List[Dict] = []
        for node_id, role, pid, sp in reversed(self.span_events):
            if trace is not None and sp[0] != trace:
                continue
            if task is not None:
                extra = sp[7] if len(sp) > 7 else None
                t = (extra or {}).get("task") or ""
                # empty t must NOT match (task.startswith("") is True for
                # every query — phase spans without a task tag are
                # reachable via their trace id, not the task filter)
                if not t or not (t.startswith(task) or task.startswith(t)):
                    continue
            out.append(_span_dict(sp, role=role, pid=pid, node_id=node_id))
            if len(out) >= limit:
                break
        out.reverse()
        return out

    async def _get_event_stats(self, conn, p) -> Dict:
        """Per-node flight-recorder health for CLI `status` (buffered /
        dropped / flushed counts per node)."""
        now = time.time()
        nodes = {}
        for node_id, st in self.event_node_stats.items():
            rings = st.get("rings", {})
            nodes[node_id] = {
                "events": st.get("events", 0),
                "spans": st.get("spans", 0),
                "flushes": st.get("flushes", 0),
                "last_flush_age_s": round(
                    now - st.get("last_flush", now), 1),
                "recorded": sum(r.get("recorded", 0)
                                for r in rings.values()),
                "clipped": sum(r.get("clipped", 0) for r in rings.values()),
                "rings": len(rings),
            }
        return {
            "nodes": nodes,
            "head": {
                "task_events_buffered": len(self.task_events),
                "spans_buffered": len(self.span_events),
                "spans_dropped": max(
                    0, self.span_events_total - len(self.span_events)),
            },
        }

    # ----------------------------------------------------------------- jobs
    async def _register_job(self, conn, p) -> None:
        self.jobs[p["job_id"]] = p
        await self._durable("job", {"key": p["job_id"], "job": dict(p)})

    async def _list_jobs(self, conn, p) -> List[Dict]:
        return list(self.jobs.values())


def main() -> None:
    import argparse

    from ray_tpu._private import sanitizer as _sanitizer

    _sanitizer.maybe_install()
    parser = argparse.ArgumentParser()
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--persist", default=os.environ.get(
        "RAY_TPU_GCS_PERSIST", ""))
    args = parser.parse_args()

    async def run():
        import signal

        from ray_tpu._private import lifecycle, proc_profile
        from ray_tpu._private.event import init_event_log, report_event

        from ray_tpu._private.protocol import set_fault_self_id

        set_fault_self_id("head")  # chaos rules may target the head
        from ray_tpu._private import events as _ev

        _ev.configure(args.session_dir, "head")
        lifecycle.register_self("gcs", args.session_dir)
        # die with the spawning driver/runner: a SIGKILL'd driver must not
        # strand the head control plane (lifecycle supervisor contract)
        lifecycle.fate_share_with_parent()
        prof = proc_profile.maybe_start()
        init_event_log(args.session_dir, "head")
        report_event("INFO", "HEAD_STARTED", "head control plane starting")
        head = HeadServer(args.session_dir, args.port,
                          persist_path=args.persist or None)
        port = await head.start()
        # Parent discovers the bound port through this file.
        with open(os.path.join(args.session_dir, "head_port"), "w") as f:
            f.write(str(port))
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        _ev.REC.dump_local("sigterm")
        # flush the last debounce window so a clean stop loses nothing;
        # the snapshot's seq stamp lets the next boot skip the WAL prefix
        head._save_state()
        if head.wal is not None:
            head.wal.close_sync()
        proc_profile.dump(prof, "head")
        lifecycle.unregister_process(args.session_dir, os.getpid())

    asyncio.run(run())


if __name__ == "__main__":
    main()
