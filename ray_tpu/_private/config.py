"""Runtime configuration flags.

Mirrors the behavior of the reference's 218-flag x-macro config table
(reference: ``src/ray/common/ray_config_def.h``): every flag has a typed
default, is overridable per-process via a ``RAY_TPU_<name>`` environment
variable, and the head node can broadcast a config dict that seeds freshly
started nodes so the whole cluster agrees on tunables.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_DEFS: Dict[str, Any] = {}
# per-flag precomputed env-override keys: building f-strings + .upper() on
# every CONFIG access showed up at ~7 accesses/task in the submit hot loop
_ENV_KEYS: Dict[str, tuple] = {}
# CPython/posix fast path: os.environ._data is a plain dict keyed by
# encodekey()'d names; both fall back cleanly when absent
_ENV_DATA = getattr(os.environ, "_data", None)
_ENCODE = getattr(os.environ, "encodekey", None)
if not isinstance(_ENV_DATA, dict) or _ENCODE is None:
    _ENV_DATA = _ENCODE = None


def _flag(name: str, default: Any) -> None:
    _DEFS[name] = default
    up, ex = f"RAY_TPU_{name.upper()}", f"RAY_TPU_{name}"
    _ENV_KEYS[name] = ((_ENCODE(up), _ENCODE(ex)) if _ENCODE is not None
                       else (up, ex))


# --- scheduling -------------------------------------------------------------
_flag("scheduler_spread_threshold", 0.5)  # hybrid policy: prefer local below this load
_flag("max_pending_lease_requests_per_scheduling_category", 10)
_flag("lease_pipeline_depth", 2)  # tasks in flight per leased worker
_flag("lease_pipeline_depth_short_task", 48)  # when exec EMA < short ms
_flag("pipeline_short_task_ms", 2.0)   # exec EMA below => deep pipeline
_flag("pipeline_medium_task_ms", 10.0)  # exec EMA below => medium pipeline
_flag("actor_batch_short_ms", 5.0)   # exec EMA below => BATCH_MAX frames
_flag("actor_batch_medium_ms", 20.0)  # exec EMA below => 16-call frames
_flag("straggler_limit_multiplier", 4.0)  # head-of-line age vs EMA
_flag("lease_pipeline_depth_medium_task", 4)  # when exec EMA < 10ms
_flag("lease_idle_ttl_ms", 250)  # idle leased workers return after this
_flag("lease_max_workers_per_pool", 256)
_flag("lease_spillback_max_hops", 4)
_flag("spill_ledger_ttl_ms", 2_000)  # in-flight spill accounting window
_flag("actor_creation_timeout_ms", 120_000)

# --- object store -----------------------------------------------------------
_flag("object_store_memory_bytes", 0)  # 0 = auto (30% of system memory)
# Cross-node transfer chunk. 1 MB beat 5 MB consistently in the two-node
# localhost sweep (0.375 vs 0.149 GB/s at window 8): smaller chunks keep
# both event loops streaming instead of stalling on multi-MB
# buffer/consume bursts. With window 8 this still keeps 8 MB in flight
# per holder on a real network.
_flag("object_chunk_size_bytes", 1024 * 1024)
_flag("inline_object_max_size_bytes", 100 * 1024)  # small returns ride the RPC reply
_flag("object_pull_deadline_s", 600)  # per-object pull budget
_flag("pull_dead_holder_rounds", 5)  # conn-dead rounds before lost verdict
_flag("object_wait_poll_ms", 200)  # store re-poll while awaiting seal
# Pull pipeline (reference: object_manager.h Push/Pull windowed chunking +
# pull_manager.h admission control): chunk requests kept in flight per
# holder connection, and the node-wide cap on unsealed pull bytes. 0 for
# the byte cap means "store capacity / 4".
_flag("object_pull_window", 8)
_flag("object_pull_max_inflight_bytes", 0)
# How long an in-flight pull survives after its LAST waiter leaves before
# being cancelled. Nonzero so a get() retried on a short timeout
# re-attaches to the running transfer instead of restarting it from byte
# 0; small so abandoned pulls stop burning bandwidth/budget long before
# the 600 s pull deadline.
_flag("object_pull_orphan_grace_s", 20.0)

# --- device object plane (ISSUE 9) ------------------------------------------
# Spanning broadcast trees: K consumers pulling the same large object are
# arranged into a tree over the per-peer data channels (interior nodes
# relay chunks while still receiving), so distribution costs O(log N)
# instead of N serial root pulls. Objects below bcast_min_bytes keep the
# plain multi-holder striped pull (tree bookkeeping costs more than it
# saves on small objects).
_flag("bcast_min_bytes", 8 * 1024 * 1024)
# Children per tree node. 2 keeps every node's upload ≤ 2x the object
# size; raise on networks where serving fan-out is cheap.
_flag("bcast_fanout", 2)
# Serve-side wait for a chunk a relay has not received yet: covers the
# parent's own admission-queue + transfer time. On expiry the child gets
# an absent verdict and re-parents through the head registry.
_flag("bcast_chunk_wait_s", 30.0)
# Parent failures one consumer tolerates (each triggers a head
# re-parent) before falling back to the plain striped pull.
_flag("bcast_max_reparents", 8)
# Idle tree state on the head is garbage-collected after this.
_flag("bcast_tree_ttl_s", 120.0)
# Tiered spill: bytes of disk the spill directory may hold before the
# oldest disk-tier objects WITH a known remote holder are demoted to the
# remote tier (local copy dropped; restore re-pulls it). 0 = unlimited.
_flag("object_spill_disk_max_bytes", 0)
# Per-node cap on object-chunk SERVING bandwidth (bytes/s, 0 =
# unlimited): a virtual-clock token bucket on FetchObjectChunk so bulk
# distribution cannot starve a node's control RPCs — and the knob that
# lets the broadcast bench model per-node upload capacity on loopback
# (where the real NIC constraint does not exist).
_flag("object_serve_bandwidth_bytes_ps", 0)

# --- object ownership ledger + leak watchdog (ISSUE 15) ----------------------
# Agent-side leak scan cadence in seconds. 0 (default) disarms the
# watchdog entirely — no loop is spawned, ledger bookkeeping stays O(1)
# dict writes per put. Armed, each scan interrogates the OWNER of every
# sealed object above object_leak_min_bytes; an object whose owner
# reports zero local refs / borrowers / task pins (or no longer knows
# it) yet remains unevicted past object_leak_grace_s is flagged, as is
# a borrow entry whose owner no longer lists the borrower.
_flag("object_leak_scan_interval_s", 0.0)
# Objects below this size are never leak-scanned (owner round trips are
# per-owner-batched, but scanning kilobyte debris is pure noise).
_flag("object_leak_min_bytes", 1024 * 1024)
# How long a zero-ref sealed object may linger before it graduates from
# candidate to suspect. 0 = flag on the second consecutive scan that
# sees it (the free path is asynchronous; one scan of slack avoids
# flagging frees in flight).
_flag("object_leak_grace_s", 0.0)
# Per-process deadline for GetObjectRefs introspection round trips
# (memory debugger fan-out + watchdog owner interrogation).
_flag("object_introspect_timeout_s", 10.0)

# --- streaming data plane (ISSUE 12) -----------------------------------------
# DataContext seeds its per-process defaults from these (env-overridable
# like every flag); the streaming shuffle + executor read the context.
# Byte budget over the input shards of ADMITTED-but-unfinished reducers
# (0 = unlimited): a slow reducer backpressures further admission instead
# of the exchange buffering the whole dataset in worker memory.
_flag("data_shuffle_inflight_bytes", 256 * 1024 * 1024)
# Map re-executions / reduce resubmissions tolerated per record before a
# shuffle loss becomes a hard ObjectLostError.
_flag("data_shuffle_max_reduce_retries", 4)
# Concurrent shuffle tasks (maps + admitted reducers + sort samples).
_flag("data_shuffle_max_concurrency", 16)
# Blocks the consumer-side iterator keeps in its prefetch window (pull
# initiated one batched WaitObjects window ahead of consumption).
_flag("data_iter_prefetch_blocks", 2)
# Event-paced executor drive loop: fallback wake period when no task
# completion / queue transition fires (liveness guard, not a poll rate).
_flag("data_exec_idle_wait_s", 0.25)

# --- workers ----------------------------------------------------------------
_flag("num_workers_soft_limit", 0)  # 0 = num_cpus
_flag("worker_startup_concurrency", 0)  # 0 = max(2, num_cpus); processes
# between fork and registration at once (reference:
# maximum_startup_concurrency, worker_pool.h)
_flag("worker_register_timeout_s", 60)
# SIGTERM->SIGKILL grace for explicitly killed actor workers. A worker
# wedged in a native collective (dead-peer rendezvous, GIL held in C++)
# never runs the Python SIGTERM handler; without escalation it dies only
# at the collective's own timeout (~100s), pinning its PG bundle and
# stalling elastic-restart actor placement behind it.
_flag("worker_kill_escalation_s", 5.0)
_flag("idle_worker_killing_time_ms", 600_000)

# --- warm worker pool (ISSUE 10) ---------------------------------------------
# Pre-warmed pool target: the agent keeps this many forked-but-idle
# workers (booted through socket handshake + store attach, parked before
# any actor-class unpickle) leasable for instant actor/task starts,
# refilling in the background (reference: worker_pool.h prestart pools).
# 0 = auto (max(2, num_cpus)); negative disables warm leasing entirely.
_flag("worker_pool_warm_target", 0)
# Background refill pacing: at most one warm fork per interval, so a
# drained pool refills without starving the workload that drained it.
_flag("worker_pool_refill_interval_ms", 50)
# Warm workers BEYOND the target that stay idle past this are reaped
# (returned leases accumulate after a burst; the target-sized core pool
# is kept warm indefinitely).
_flag("worker_pool_idle_ttl_s", 30.0)
# Predictive demand-paged refill (ISSUE 11): an actor start that misses
# the warm pool WAITS for the next pool registration (instead of always
# cold-forking), and the refill loop sizes its fork burst from the
# observed CreateActorBatch window + the live waiter queue — not one
# fork per tick — so hit_ratio approaches 1 under creation bursts.
# How long a missed actor start waits for a demand-paged pool worker
# before falling back to a dedicated cold fork (never a failure mode).
_flag("worker_pool_wait_s", 20.0)
# How long StartActor(Batch) demand is remembered: the instantaneous
# batch size + live waiter queue drive the pre-fork burst; this window
# only scopes the `recent_demand` observability field (pool stats, CLI)
# and bounds the demand ledger's size.
_flag("worker_pool_demand_window_s", 5.0)
# Cap on pool-fill forks enqueued per refill decision; 0 = uncapped
# (the spawn admission queue still bounds concurrent boots).
_flag("worker_pool_refill_burst_max", 0)

# --- multiplexed direct-call plane (ISSUE 11) --------------------------------
# One ctrl connection per peer PROCESS carrying every actor/lease/owner
# channel as a stream (per-call stream ids in the PR 3 framing) instead
# of one TCP connection per driver→actor pair. Per-stream close fails
# only that stream's in-flight calls; the session survives for its
# siblings.
# Fair interleaving quantum: frames one stream may place in the shared
# session's outbound buffer per round-robin turn, so one chatty actor
# cannot head-of-line-block its session siblings' dispatch order.
_flag("direct_call_fair_frames_per_round", 16)

# --- shared-memory local RPC (ISSUE 11) ---------------------------------------
# Same-node sessions attach a shm doorbell lane riding the store arena
# mount: an SPSC ring per direction + a FIFO doorbell, selected
# automatically when caller and callee share a node_id. Frames above
# shm_rpc_max_frame_bytes (or with the ring full) transparently fall
# back to the session's TCP lane; a session-seq reorder stage on the
# receiver keeps cross-lane dispatch order identical to a single TCP
# stream. Cross-node peers and arena-less processes never attach.
_flag("shm_rpc_enabled", True)
_flag("shm_rpc_ring_bytes", 4 * 1024 * 1024)  # per direction
_flag("shm_rpc_max_frame_bytes", 256 * 1024)  # larger frames ride TCP
_flag("shm_rpc_attach_timeout_s", 5.0)  # ShmAttach handshake budget
# Reorder-stage gap deadline: a cross-lane frame missing this long (a
# fault-injected drop on one lane) is given up on — later frames
# dispatch out of order instead of stalling the session forever.
_flag("shm_rpc_order_gap_s", 10.0)

# --- batched control RPCs (ISSUE 10) -----------------------------------------
# Driver-side CreateActor coalescing: anonymous (unnamed, not
# get_if_exists) creates enqueue for up to this window and ride ONE
# CreateActorBatch RPC + one WAL group-commit instead of N serial round
# trips. 0 disables (every create is a blocking RPC again).
_flag("actor_create_batch_window_ms", 4.0)
_flag("actor_create_batch_max", 256)  # flush immediately at this size
# Agent-side ActorReady relay coalescing: workers report readiness to
# their node agent (unix socket); the agent flushes one ActorReadyBatch
# head RPC per window, acking workers only after the head acked.
_flag("actor_ready_batch_window_ms", 5.0)

# --- fault tolerance --------------------------------------------------------
_flag("task_max_retries_default", 3)
_flag("actor_max_restarts_default", 0)
_flag("health_check_period_ms", 3_000)
_flag("health_check_failure_threshold", 5)
# --- lineage reconstruction (ISSUE 17) ---------------------------------------
# Owner-side lineage ledger cap: serialized replayable task specs are
# retained while any plasma return is still referenced, up to this many
# bytes; past the cap the oldest records are evicted (their objects
# become non-reconstructable, like the reference's
# max_lineage_bytes / task_manager.h:202 evict-on-cap).
_flag("lineage_max_bytes", 64 * 1024 * 1024)
# Chain-reconstruction bounds: how deep a recursive argument-replay
# chain may go, and how many times any single object may be
# reconstructed, before a typed ObjectReconstructionFailedError
# surfaces instead of resubmitting again.
_flag("lineage_max_reconstruction_depth", 20)
_flag("lineage_max_reconstruction_attempts", 3)
# Reconnect grace after an agent's TCP connection drops: a transient
# blip (head restart, one lost socket) no longer instantly kills a
# healthy node's actors — the node is only marked dead if it fails to
# re-register within the window. Keep BELOW the heartbeat budget
# (health_check_period_ms * health_check_failure_threshold), which stays
# the authoritative liveness verdict for silent (partitioned) nodes.
_flag("node_disconnect_grace_s", 5.0)
# Application-level idle deadline for direct worker/actor channels: with
# calls outstanding and the channel silent past this, a ping probes it;
# an unanswered probe fails every pending call with ConnectionLost
# (partitions never RST). 0 disables. A ping that round-trips proves
# liveness, so long-running remote methods never trip this.
_flag("client_idle_deadline_s", 0.0)
# Default deadline for fire-and-check control RPCs (publishes, KV puts,
# registrations, death reports — anything the server answers immediately).
# Under a one-way partition the request is silently eaten (no TCP RST)
# and an untimed .call parks its caller forever (the pre-PR 5 watchdog
# wedge); raylint R6 requires every control .call to be bounded, and this
# is the budget those sites reach for. Generous: it only has to beat
# "forever", not the health-check verdict. Long-poll RPCs (lease grants,
# object-seal waits) are exempt by design and carry inline raylint
# disables at the call site.
_flag("control_rpc_timeout_s", 60.0)
# Bounded-retry-with-jitter defaults for idempotent control RPCs
# (protocol.retry_call): attempts, base backoff, backoff cap.
_flag("rpc_retry_max_attempts", 5)
_flag("rpc_retry_base_s", 0.1)
_flag("rpc_retry_max_s", 2.0)

# --- control plane ----------------------------------------------------------
_flag("gossip_period_ms", 100)  # resource-view sync cadence (ray_syncer analog)
# Collective payloads above this ride the object plane (put/get between
# members, worker<->worker); below it they inline through the rendezvous
# store (one RPC beats put+get for metadata-sized tensors).
_flag("collective_inline_max_bytes", 65536)
_flag("metrics_report_interval_ms", 5_000)
# Prometheus scrape endpoint on the head (ISSUE 14): a minimal asyncio
# HTTP server answering GET /metrics with the merged cluster exposition
# text. 0 = disabled; the bound port is written to <session>/metrics_port
# so `ray_tpu metrics --scrape` and tests can find it.
_flag("metrics_export_port", 0)
_flag("task_event_buffer_max", 100_000)

# --- cluster flight recorder (ISSUE 14) --------------------------------------
# Fraction of trace ROOTS (task submits, puts, gets, pulls, engine
# steps) that record span trees; children inherit the parent's verdict
# via the trace context on the task-spec wire. 0 (default) disarms the
# recorder entirely — every instrumentation site is then one attribute
# load + branch (events.overhead_probe measures it). Set to 1.0 when
# debugging where time goes per hop.
_flag("task_event_sample_rate", 0.0)
# Per-process ring geometry: fixed-size mmap'd slots under
# <session>/events/<role>-<pid>.ring. The file IS the flight recorder —
# a kill -9'd process's spans are recovered from it with no exit handler.
_flag("task_event_ring_slots", 4096)
_flag("task_event_ring_slot_bytes", 256)
# Head-side span ring (deque maxlen) fed by ReportTaskEvents flushes.
_flag("task_event_span_buffer_max", 200_000)
# Executor workers flush spans to the head at most this often (drivers
# flush on the watchdog tick + synchronously from timeline()).
_flag("task_event_flush_interval_s", 1.0)
_flag("task_event_flush_batch", 5000)  # size backstop between periodic
# flushes (the watchdog's periodic flush is the normal path — reference
# flushes on a 1s timer, task_events_report_interval_ms; a small size
# trigger made every 50th task in a burst pay a head round-trip)
_flag("rpc_drain_threshold_bytes", 64 * 1024)  # write-combining flush point
_flag("head_watchdog_period_s", 2.0)  # driver head-liveness probes
# Executor workers probe the head far less often (ISSUE 10): their head
# link only serves actor resolution / task events — reconnect-after-
# restart can lag — while at 1,000 workers a 2s ping each means 500
# head RPCs/s of pure liveness noise. Node liveness stays the agent's
# 2s watchdog; connection loss still fails fast via the read loop.
_flag("worker_head_watchdog_period_s", 15.0)
_flag("agent_head_gone_exit_s", 120.0)  # agent suicide after head unreachable
_flag("autoscaler_boot_timeout_s", 120.0)  # launched-node registration window

# --- round-3 sweep: formerly hardcoded timeouts/backoffs ---------------------
_flag("head_ping_timeout_s", 5.0)  # watchdog ping RPC deadline
_flag("worker_spawn_retry_s", 0.5)  # backoff when the pool is saturated
_flag("object_locate_timeout_s", 15.0)  # owner-directory lookups
_flag("object_chunk_fetch_timeout_s", 60.0)  # one cross-node chunk RPC
_flag("object_pull_retry_s", 0.2)  # pull-plane retry backoff
_flag("owned_resolve_timeout_s", 10.0)  # owner metadata resolution
_flag("borrow_resolve_timeout_s", 15.0)  # borrowed-object owner round trip
_flag("actor_probe_timeout_s", 5.0)  # liveness probe on a silent actor
_flag("actor_reconnect_backoff_s", 0.2)  # actor-client reconnect pacing
_flag("lease_retry_backoff_s", 0.2)  # lease-request retry pacing
_flag("actor_call_batch_max", 64)  # specs per PushTaskBatch frame

# --- submission/completion fast path (ISSUE 18) ------------------------------
# The driver-side fast path: spec-template cache on the per-call submit
# paths, vectorized submit_many/fn.map, and the batched completion
# delivery queue (task replies landing in one loop tick resolve through
# one memory-store put_batch + one ref-counter pass instead of a lock
# round trip per return).
# Frozen spec templates cached per (function id, options hash); cap with
# clear-on-cap like the callsite cache — real programs have a bounded set
# of (function, options) signatures, and a clear simply re-freezes.
_flag("spec_template_cache_max", 512)

# --- round-3 sweep 2: poll cadences + 2PC/bootstrap deadlines ----------------
_flag("actor_resource_wait_poll_s", 0.1)  # actor waiting on node/PG capacity
# Fallback poll for the agent's hold-resources-until-death watcher. The
# watcher is event-driven (WorkerHandle.exited); this bounds release lag
# only for death paths that miss the event.
_flag("actor_liveness_poll_s", 5.0)
_flag("object_unlocated_retry_s", 0.1)  # owner knows no location yet
_flag("object_pull_round_s", 0.2)  # pull-plane round pacing
# Snapshot write coalescing window. The snapshot is O(cluster state) and
# is rebuilt on the head loop (+ pickled under the GIL): at 0.05s a
# 1,000-actor creation burst spent ~20 full-state saves/s on the one
# core that also schedules the burst. 0.25s bounds the durability gap
# while cutting that 5x (Redis-backed HA is the real durability path).
_flag("head_save_debounce_s", 0.25)
_flag("pg_prepare_timeout_s", 10.0)  # 2PC bundle-prepare RPC deadline

# --- head-plane durability (ISSUE 8) ----------------------------------------
# WAL rides next to a file-backed RAY_TPU_GCS_PERSIST store: every
# authoritative mutation is appended + fsynced BEFORE its RPC is acked,
# so kill -9 at any point loses nothing acknowledged.
# Group-commit window: appends buffer up to this long so one fsync
# covers a whole mutation burst. 0 = fsync every batch immediately.
_flag("gcs_wal_fsync_interval_ms", 2.0)
# Snapshot-and-truncate compaction threshold for the WAL file.
_flag("gcs_wal_compact_bytes", 8 * 1024 * 1024)
# Recovery claim window: entities restored from the durable store stay
# RECOVERING this long for their agent/driver to re-register and claim
# them; anything unclaimed is then declared dead with reason
# "lost_during_head_outage". Keep comfortably above
# head_watchdog_period_s so healthy agents always make the window.
_flag("gcs_recovery_grace_s", 10.0)
# How long head-bound control calls queue (retrying while the watchdog
# reconnects) during a head outage before failing fast with a typed
# HeadUnavailableError. 0 = fail on first connection loss.
_flag("gcs_outage_queue_s", 30.0)
_flag("pg_retry_place_period_s", 0.5)  # pending-PG placement retry cadence
_flag("pg_resolve_poll_s", 0.1)  # lease pool waiting for PG placement
_flag("wait_poll_interval_s", 0.002)  # ray.wait readiness re-check
_flag("node_boot_poll_s", 0.02)  # head/agent subprocess startup polling
_flag("worker_park_poll_s", 2.0)  # worker main-thread liveness park
# (2s: the park check is a fallback — PDEATHSIG + the agent connection
# drop are the fast death paths; at 1,000 workers a 0.5s poll was 2,000
# wakeup syscalls/s of background burn)
_flag("conda_failure_cache_s", 60.0)  # failed-env fast-fail window

# --- TPU --------------------------------------------------------------------
_flag("tpu_chips_per_host_default", 4)

# --- elastic training plane -------------------------------------------------
# An in-store shard rides alongside every disk checkpoint, so restarts
# restore through the broadcast-tree pull path without disk reads. This
# many in-store sharded checkpoints stay pinned by the driver; older
# manifests unpin their shards back to LRU eviction
_flag("train_in_store_keep", 2)
# bound on one collective-rendezvous attempt (jax.distributed.initialize
# + group formation) — the rc-124 hang class becomes a typed retry
_flag("train_rendezvous_timeout_s", 120.0)
# bounded rendezvous attempts, fresh coordinator port each (free-port race)
_flag("train_rendezvous_max_retries", 3)
# one result round's sync-barrier deadline in BackendExecutor
_flag("train_result_timeout_s", 3600.0)

# --- logging / debug --------------------------------------------------------
_flag("log_to_driver", True)
# RAY_TPU_SANITIZE=1: wrap threading locks to record acquisition order
# (checked against raylint R12's static lock-order graph) and assert
# thread-affinity calibration on marked hot-path mutations; see
# _private/sanitizer.py. Debug builds only — the disabled path is a
# single module-level bool check (<2% like the flight recorder).
_flag("sanitize", False)


class _Config:
    """Flag accessor: attribute access returns the effective value
    (env override > cluster broadcast > default)."""

    def __init__(self):
        self._overrides: Dict[str, Any] = {}

    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        if name not in _DEFS:
            raise AttributeError(f"unknown config flag: {name}")
        # accept both RAY_TPU_FLAG_NAME (conventional) and the exact
        # lowercase flag name; env stays authoritative on EVERY read (tests
        # flip flags mid-process) — the raw environ dict makes that a plain
        # dict lookup instead of two MutableMapping round-trips per access
        upper_key, exact_key = _ENV_KEYS[name]
        data = _ENV_DATA
        if data is not None:
            raw = data.get(upper_key)
            if raw is None:
                raw = data.get(exact_key)
            if raw is not None:
                return _coerce(os.fsdecode(raw), _DEFS[name])
        else:  # non-CPython/exotic platform fallback
            for env_key in (upper_key, exact_key):
                if env_key in os.environ:
                    return _coerce(os.environ[env_key], _DEFS[name])
        if name in self._overrides:
            return self._overrides[name]
        return _DEFS[name]

    def apply_cluster_config(self, cfg: Dict[str, Any]) -> None:
        """Apply the head-broadcast config dict (lower priority than env)."""
        for k, v in cfg.items():
            if k in _DEFS:
                self._overrides[k] = v

    def snapshot(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in _DEFS}

    def to_json(self) -> str:
        return json.dumps(self.snapshot())


def _coerce(raw: str, default: Any) -> Any:
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


CONFIG = _Config()


def keep_off_accelerator(env: dict) -> dict:
    """Pin a child-process env to the CPU platform (in place; returned for
    chaining). Control-plane daemons never touch jax, and a worker holds
    no chip until a lease names one: a child that inherited the driver's
    platform choice would take the chip from the process it was leased
    to. A chip lease overrides this in the worker before its first jax
    import (worker_process._apply_accelerator_env). ONE implementation
    for every spawn site."""
    env["JAX_PLATFORMS"] = "cpu"
    return env


def whole_malloc_heaps(env: dict) -> dict:
    """Let a worker's threads allocate as cheaply as its main thread does,
    unless its launcher chose (in place; returned for chaining). A worker
    runs what it is asked off its main thread, and glibc hands every other
    thread an arena of its own: heaps of 64 MB that it reserves and then
    opens a page at a time, one ``mprotect`` for each 4 KB the thread comes
    to need (``sysmalloc`` pads the main arena's break by ``M_TOP_PAD`` and
    a thread's heap by nothing), where a heap born with that pad is opened
    whole. In a sandboxed kernel ``mprotect`` is dear, and work that
    allocates much is four times slower off the main thread for nothing:
    ``jax.profiler.stop_trace()`` over 407 000 device events took 49.3 s
    from a thread and 12.8 s from the main thread of the same process;
    with the pad at a heap's size 12.5 s from a thread (one TPU v5e host
    under gVisor; PERF.md section 6, PR 42). One arena for all threads
    (``MALLOC_ARENA_MAX=1``) does as much for that call and makes a cold
    XLA compile, which allocates from many threads at once, three times
    longer (31.3 s against 11.2): not that. Naming the pad freezes glibc's
    moving ``mmap`` threshold at its first 128 KB, so that is named too, at
    the 32 MB the moving one ends at. ``ptmalloc`` reads both once, when
    the process starts: so they go into the environment the worker, or the
    template it is forked from, is EXECUTED with, not into the one it is
    handed after the fork."""
    env.setdefault("MALLOC_TOP_PAD_", str(64 << 20))
    env.setdefault("MALLOC_MMAP_THRESHOLD_", str(32 << 20))
    return env
