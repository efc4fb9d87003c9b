"""State API (reference: python/ray/util/state/api.py — list_actors :782,
list_tasks :1014, summaries :1376; aggregated by
dashboard/state_aggregator.py StateAPIManager :141).

Queries go to the head's info handlers; per-worker live state rides the
task-event store the way the reference pairs GCS data with
``QueryAllWorkerStates``.
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional

__all__ = [
    "list_actors", "list_nodes", "list_tasks", "list_placement_groups",
    "list_jobs", "list_workers", "list_objects", "object_summary",
    "summarize_tasks", "summarize_actors", "summarize_objects",
    "get_node_stats", "profile_worker", "capture_jax_trace",
    "list_cluster_events",
]


def _worker():
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker
    if w is None or not w.connected:
        raise RuntimeError("ray_tpu.init() must be called first")
    return w


def _call(method: str, payload: Optional[Dict] = None):
    w = _worker()
    return w._acall(w.head.call(method, payload or {}))


def _apply_filters(rows: List[Dict], filters) -> List[Dict]:
    """filters: [(key, op, value)] with op in ('=', '!=', '<', '<=',
    '>', '>=', 'contains', '!contains') — the reference's predicate set
    (reference: python/ray/util/state/api.py StateApiClient filters +
    common.py supported_filters). Ordering ops compare numerically when
    both sides parse as floats, else lexically."""

    def _cmp(a, b) -> Optional[int]:
        try:
            fa, fb = float(a), float(b)
            return (fa > fb) - (fa < fb)
        except (TypeError, ValueError):
            sa, sb = str(a), str(b)
            return (sa > sb) - (sa < sb)

    for key, op, value in filters or []:
        if op == "=":
            rows = [r for r in rows if str(r.get(key)) == str(value)]
        elif op == "!=":
            rows = [r for r in rows if str(r.get(key)) != str(value)]
        elif op in ("<", "<=", ">", ">="):
            want = {"<": (-1,), "<=": (-1, 0), ">": (1,), ">=": (0, 1)}[op]
            rows = [r for r in rows
                    if r.get(key) is not None
                    and _cmp(r.get(key), value) in want]
        elif op == "contains":
            rows = [r for r in rows if r.get(key) is not None
                    and str(value) in str(r.get(key))]
        elif op == "!contains":
            rows = [r for r in rows if r.get(key) is not None
                    and str(value) not in str(r.get(key))]
        else:
            raise ValueError(f"unsupported filter op {op!r}")
    return rows


def list_actors(filters=None, limit: int = 1000) -> List[Dict]:
    rows = _call("ListActors")
    return _apply_filters(rows, filters)[:limit]


def list_nodes(filters=None, limit: int = 1000) -> List[Dict]:
    from ray_tpu._private.resources import ResourceSet

    rows = _call("ListNodes")
    for r in rows:
        r["state"] = "ALIVE" if r.get("alive") else "DEAD"
        for key in ("resources_total", "resources_available"):
            if isinstance(r.get(key), dict):
                r[key] = ResourceSet.from_wire(r[key]).to_dict()
    return _apply_filters(rows, filters)[:limit]


def list_tasks(filters=None, limit: int = 10000) -> List[Dict]:
    w = _worker()
    w.flush_task_events()
    payload: Dict = {"limit": limit * 4}
    # an equality filter on job_id prefilters server-side — the head
    # scans its 100k-entry ring once instead of shipping 4x limit rows
    # for the client to discard
    for key, op, value in filters or []:
        if key == "job_id" and op == "=":
            payload["job_id"] = value
            break
    rows = _call("ListTaskEvents", payload)
    return _apply_filters(rows, filters)[:limit]


def list_placement_groups(filters=None, limit: int = 1000) -> List[Dict]:
    rows = _call("ListPlacementGroups")
    return _apply_filters(rows, filters)[:limit]


def list_jobs(filters=None, limit: int = 1000) -> List[Dict]:
    rows = _call("ListJobs")
    return _apply_filters(rows, filters)[:limit]


def _call_agent(addr: Dict, method: str, payload: Optional[Dict] = None):
    """Live per-node query straight to a node agent (reference: the state
    API pairs GCS tables with NodeManager::QueryAllWorkerStates)."""
    w = _worker()

    async def go():
        client = await w._owner_client(addr)
        return await client.call(method, payload or {}, timeout=10)

    return w._acall(go())


def _each_alive_agent():
    for node in _call("ListNodes"):
        if node.get("alive") and node.get("addr"):
            yield node


def list_workers(filters=None, limit: int = 1000) -> List[Dict]:
    """All worker processes across the cluster (reference:
    util/state/api.py list_workers)."""
    rows: List[Dict] = []
    for node in _each_alive_agent():
        try:
            rows.extend(_call_agent(node["addr"], "ListWorkers"))
        except Exception:
            continue  # node died mid-listing
        if len(rows) >= limit:
            break
    return _apply_filters(rows, filters)[:limit]


def list_objects(filters=None, limit: int = 1000,
                 detail: bool = True) -> List[Dict]:
    """Every owned object across the cluster with creation provenance —
    callsite, creator task/actor, size, refs, residency tier (ISSUE 15;
    reference: util/state/api.py list_objects over core-worker object
    views). ``detail=False`` falls back to the raw per-node store
    listing (no owner join — objects whose owner died still show)."""
    if detail:
        out = _call("ObjectSummary", {"detail": True, "limit": limit})
        return _apply_filters(out.get("rows") or [], filters)[:limit]
    rows: List[Dict] = []
    for node in _each_alive_agent():
        try:
            rows.extend(_call_agent(node["addr"], "ListStoreObjects",
                                    {"limit": limit}))
        except Exception:
            continue
        if len(rows) >= limit:
            break
    return _apply_filters(rows, filters)[:limit]


def list_cluster_events(severity: Optional[str] = None,
                        label: Optional[str] = None,
                        limit: int = 1000) -> List[Dict]:
    """Structured cluster events — node deaths, actor failures, OOM kills,
    autoscaler actions (reference: src/ray/util/event.h RAY_EVENT files
    surfaced by the dashboard event module)."""
    import os

    import ray_tpu
    from ray_tpu._private.event import read_events

    node = ray_tpu._global_node
    session_dir = (node.session_dir if node is not None
                   else os.environ.get("RAY_TPU_SESSION_DIR"))
    out: List[Dict] = []
    if session_dir:
        out.extend(read_events(session_dir, severity=severity,
                               label=label, limit=limit))
    # aggregate remote nodes' events (their session dirs live on their
    # machines); de-dup against the local read for shared-dir test setups
    seen = {(e.get("component"), e.get("pid"), e.get("timestamp"))
            for e in out}
    for n in _each_alive_agent():
        try:
            remote = _call_agent(n["addr"], "ListEvents",
                                 {"severity": severity, "label": label,
                                  "limit": limit})
        except Exception:
            continue
        for e in remote:
            key = (e.get("component"), e.get("pid"), e.get("timestamp"))
            if key not in seen:
                seen.add(key)
                out.append(e)
    out.sort(key=lambda e: e.get("timestamp", 0.0))
    return out[-limit:]


def get_node_stats() -> List[Dict]:
    """Per-node reporter samples: cpu/mem/disk/workers/object-store/TPU
    (reference: dashboard reporter_agent.py:277 stats surface)."""
    rows = []
    for node in _each_alive_agent():
        try:
            stats = _call_agent(node["addr"], "GetNodeStats")
        except Exception:
            continue
        if stats:
            rows.append(stats)
    return rows


def _worker_direct_addr(worker_id: str) -> Dict:
    for w in list_workers(limit=100000):
        if w["worker_id"] == worker_id and w.get("direct_addr") \
                and w.get("alive"):
            return w["direct_addr"]
    raise ValueError(f"no live worker {worker_id!r} with a direct address")


def profile_worker(worker_id: str, duration_s: float = 2.0) -> Dict:
    """Sample a worker's Python stacks (py-spy analog; reference:
    dashboard/modules/reporter/profile_manager.py:61-97). Returns
    {"pid", "duration_s", "folded": {stack: count}} — folded-stacks text
    for flamegraph.pl / speedscope."""
    addr = _worker_direct_addr(worker_id)
    w = _worker()

    async def go():
        client = await w._owner_client(addr)
        return await client.call("SampleStacks",
                                 {"duration_s": duration_s},
                                 timeout=duration_s + 30)

    return w._acall(go(), timeout=duration_s + 35)


def capture_jax_trace(worker_id: str, duration_s: float = 2.0,
                      out_dir: Optional[str] = None) -> Dict:
    """Capture a jax.profiler device trace inside a worker (SURVEY §5 —
    device-trace profiling surfaced through the same reporter API).
    Returns {"trace_dir", "files"} loadable in TensorBoard/Perfetto."""
    addr = _worker_direct_addr(worker_id)
    w = _worker()

    async def go():
        client = await w._owner_client(addr)
        # generous window: jax.profiler start/stop can take tens of
        # seconds beyond the capture itself
        return await client.call(
            "CaptureJaxTrace",
            {"duration_s": duration_s, "out_dir": out_dir},
            timeout=duration_s + 180)

    return w._acall(go(), timeout=duration_s + 185)


def summarize_objects(group_by: str = "node") -> Dict[str, Any]:
    """Cluster object totals grouped by ``node`` / ``callsite`` /
    ``creator`` / ``tier`` (reference: ``ray summary objects`` +
    ``ray memory`` group-by; the head's ObjectSummary does the
    fan-out + merge)."""
    if group_by not in ("node", "callsite", "creator", "tier"):
        raise ValueError(
            f"group_by must be node|callsite|creator|tier, got {group_by!r}")
    out = _call("ObjectSummary", {"group_by": group_by, "limit": 100000})
    return out.get("groups") or {}


def object_summary(group_by: str = "node", detail: bool = False,
                   limit: int = 10000) -> Dict[str, Any]:
    """Full ObjectSummary reply: per-node store/tier stats, leak
    suspects, groups, and (with detail) per-object provenance rows —
    what ``ray_tpu memory`` renders."""
    return _call("ObjectSummary", {"group_by": group_by, "detail": detail,
                                   "limit": limit})


def summarize_tasks() -> Dict[str, Dict]:
    """Per-function-name counts by state (reference: ``ray summary tasks``)."""
    by_name: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    for e in list_tasks():
        by_name[e.get("name", "?")][e.get("state", "?")] += 1
    return {name: dict(states) for name, states in by_name.items()}


def summarize_actors() -> Dict[str, Dict]:
    by_class: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    for a in list_actors():
        by_class[a.get("class_name", "?")][a.get("state", "?")] += 1
    return {cls: dict(states) for cls, states in by_class.items()}
