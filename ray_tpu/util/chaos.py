"""Chaos / fault-injection utilities (reference:
python/ray/_private/test_utils.py:1431 ResourceKillerActor hierarchy and
python/ray/tests/chaos/ — periodic killers that chaos tests aim at the
cluster while a workload runs; recovery machinery, not the workload, is
what's under test).

Killers run in the DRIVER process on a background thread (they must
survive the very failures they inject — an actor-based killer can be
scheduled onto the node it kills). Targets come from the live cluster
state, so the same killer works against ``cluster_utils.Cluster``
fixtures and real deployments.
"""

from __future__ import annotations

import json
import os
import random
import signal
import threading
from typing import Callable, Dict, List, Optional

import ray_tpu


class ResourceKiller:
    """Base: periodically pick a target and kill it until stopped."""

    def __init__(self, interval_s: float = 1.0,
                 max_kills: Optional[int] = None,
                 seed: Optional[int] = None):
        self.interval_s = interval_s
        self.max_kills = max_kills
        self.rng = random.Random(seed)
        self.kills: List[str] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- subclass hooks ----------------------------------------------------
    def find_target(self):
        raise NotImplementedError

    def kill_target(self, target) -> Optional[str]:
        """Kill; return a human-readable record or None if it got away."""
        raise NotImplementedError

    # -- lifecycle ---------------------------------------------------------
    def run(self) -> "ResourceKiller":
        def loop():
            while not self._stop.is_set():
                if self.max_kills is not None and \
                        len(self.kills) >= self.max_kills:
                    return
                try:
                    target = self.find_target()
                    if target is not None:
                        record = self.kill_target(target)
                        if record:
                            self.kills.append(record)
                except Exception:
                    pass  # the cluster may be mid-recovery; try again
                self._stop.wait(self.interval_s)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name=type(self).__name__)
        self._thread.start()
        return self

    def stop(self) -> List[str]:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=10)
        return list(self.kills)


class WorkerKiller(ResourceKiller):
    """SIGKILL random task/actor worker processes on the local node
    (reference: WorkerKillerActor). Workers are discovered through the
    agent's ListWorkers RPC; the driver's own pid is never a target."""

    def __init__(self, interval_s: float = 1.0,
                 max_kills: Optional[int] = None,
                 seed: Optional[int] = None,
                 filter_fn: Optional[Callable[[dict], bool]] = None):
        super().__init__(interval_s, max_kills, seed)
        self.filter_fn = filter_fn

    def find_target(self):
        worker = ray_tpu._private.worker.global_worker
        reply = worker._acall(
            worker.agent.call("ListWorkers", {}), timeout=10)
        candidates = [
            w for w in (reply or [])
            if w.get("pid") and w["pid"] != os.getpid()
            # busy workers only: killing idle pool processes is no chaos
            and w.get("state") in ("LEASED", "ACTOR")
            and (self.filter_fn is None or self.filter_fn(w))
        ]
        return self.rng.choice(candidates) if candidates else None

    def kill_target(self, target) -> Optional[str]:
        try:
            os.kill(target["pid"], signal.SIGKILL)
            return f"worker pid={target['pid']}"
        except ProcessLookupError:
            return None


class NodeKiller(ResourceKiller):
    """Kill a random non-head node's agent process (reference:
    RayletKiller / EC2InstanceTerminator). Operates on a
    ``cluster_utils.Cluster`` so the process handles are killable."""

    def __init__(self, cluster, interval_s: float = 2.0,
                 max_kills: Optional[int] = None,
                 seed: Optional[int] = None):
        super().__init__(interval_s, max_kills, seed)
        self.cluster = cluster

    def find_target(self):
        nodes = [n for n in self.cluster.worker_nodes
                 if n.agent_proc and n.agent_proc.poll() is None]
        return self.rng.choice(nodes) if nodes else None

    def kill_target(self, target) -> Optional[str]:
        node_id = target.node_id
        self.cluster.remove_node(target, allow_graceful=False)
        return f"node {node_id[:12]}"


class DaemonKiller(ResourceKiller):
    """SIGKILL registered session daemons (agent / forkserver / gcs /
    worker) picked from the lifecycle pid registry — the chaos probe for
    the teardown supervisor itself: after a kill, fate-sharing must reap
    the victim's subtree and the session registry must converge to zero
    live pids on shutdown."""

    def __init__(self, session_dir: str, roles=("agent",),
                 interval_s: float = 2.0, max_kills: Optional[int] = None,
                 seed: Optional[int] = None, filter_fn=None):
        super().__init__(interval_s, max_kills, seed)
        self.session_dir = session_dir
        self.roles = tuple(roles)
        # optional registry-record predicate to pin the victim further
        # than role alone (e.g. "the worker hosting train rank 0")
        self.filter_fn = filter_fn

    def find_target(self):
        from ray_tpu._private import lifecycle

        candidates = [
            r for r in lifecycle.live_registered(self.session_dir)
            if r.get("role") in self.roles and r["pid"] != os.getpid()
            and (self.filter_fn is None or self.filter_fn(r))
        ]
        return self.rng.choice(candidates) if candidates else None

    def kill_target(self, target) -> Optional[str]:
        try:
            os.kill(target["pid"], signal.SIGKILL)
            return f"{target.get('role', 'daemon')} pid={target['pid']}"
        except ProcessLookupError:
            return None


class NetworkPartitioner(ResourceKiller):
    """Partition nodes off the cluster's NETWORK without touching their
    processes (built on protocol.FaultSchedule — reference lineage: the
    Jepsen/mesh-partition testing tradition the process killers above
    cannot reach). The victim's daemons stay alive and its sockets stay
    open; frames just stop flowing, which is exactly the failure mode —
    hung host, one-way link, gray failure — that RST-driven recovery
    paths never see.

    Requires the cluster to run with ``RAY_TPU_FAULT_INJECTION=1`` in the
    daemons' environment (set it before ``Cluster()``/``init()``); rules
    are published through ``<session_dir>/fault_schedule.json`` and
    picked up by every process within ``protocol.FAULT_POLL_S``.

    Modes: ``"both"`` (symmetric partition), ``"out"`` (one-way: the node
    hears the cluster but nothing it says gets out — heartbeats vanish,
    no RST), ``"in"`` (the node goes deaf). Unix sockets (worker ↔ local
    agent) are spared: the HOST is healthy, its network is not.

    Use directly (``partition(node_id)`` / ``heal()``) or as a periodic
    killer: each round partitions a random worker node for
    ``duration_s``, then heals it.
    """

    def __init__(self, cluster=None, session_dir: Optional[str] = None,
                 mode: str = "both", duration_s: float = 10.0,
                 interval_s: float = 5.0, max_kills: Optional[int] = None,
                 seed: Optional[int] = None):
        super().__init__(interval_s, max_kills, seed)
        if session_dir is None:
            if cluster is None:
                raise ValueError("need a cluster or a session_dir")
            session_dir = cluster.session_dir
        self.cluster = cluster
        self.session_dir = session_dir
        self.mode = mode
        self.duration_s = duration_s
        self.partitioned: Dict[str, str] = {}  # node_id -> mode
        self._rules_lock = threading.Lock()

    @property
    def fault_file(self) -> str:
        return os.path.join(self.session_dir, "fault_schedule.json")

    def _write_rules(self) -> None:
        rules = []
        for node_id, mode in self.partitioned.items():
            directions = {"both": ["both"], "out": ["out"],
                          "in": ["in"]}[mode]
            for direction in directions:
                rules.append({"self": node_id, "peer": "tcp",
                              "direction": direction, "method": "*",
                              "action": "drop"})
        tmp = self.fault_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"rules": rules}, f)
        os.replace(tmp, self.fault_file)  # atomic: pollers never see a
        # half-written schedule

    def partition(self, node_id: str, mode: Optional[str] = None) -> None:
        """Cut node `node_id` off per `mode`, effective within one poll."""
        with self._rules_lock:
            self.partitioned[node_id] = mode or self.mode
            self._write_rules()

    def heal(self, node_id: Optional[str] = None) -> None:
        """Restore connectivity for one node (or all)."""
        with self._rules_lock:
            if node_id is None:
                self.partitioned.clear()
            else:
                self.partitioned.pop(node_id, None)
            self._write_rules()

    # -- ResourceKiller hooks ---------------------------------------------
    def find_target(self):
        head_id = None
        if self.cluster is not None and self.cluster.head_node is not None:
            head_id = self.cluster.head_node.node_id
        try:
            nodes = [n["node_id"] for n in ray_tpu.nodes()
                     if n["alive"] and n["node_id"] != head_id
                     and n["node_id"] not in self.partitioned]
        except Exception:
            return None
        return self.rng.choice(nodes) if nodes else None

    def kill_target(self, target) -> Optional[str]:
        self.partition(target)
        timer = threading.Timer(self.duration_s, self.heal, args=(target,))
        timer.daemon = True
        timer.start()
        return f"partition {target[:12]} mode={self.mode}"

    def stop(self) -> List[str]:
        kills = super().stop()
        self.heal()  # never leave a standing partition behind
        return kills


def kill_random_node(cluster, exclude_head: bool = True) -> Optional[str]:
    """One-shot helper (the `ray kill-random-node` CLI analog)."""
    killer = NodeKiller(cluster, max_kills=1)
    target = killer.find_target()
    if target is None:
        return None
    return killer.kill_target(target)
