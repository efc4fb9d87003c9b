"""Cluster flight recorder (ISSUE 14).

A per-process, lock-cheap, bounded ring of compact span events recording
the lifecycle of tasks (submit → lease-wait → exec → return-put), objects
(put, pull admission, broadcast relay, spill restore) and actor calls
(enqueue → dispatch → reply), with a trace/span-id context that rides the
task-spec wire so one ``ray_tpu.get()`` stitches into a single
cross-process trace tree (reference: the GCS task-event plane +
``ray timeline``, task_event_buffer.h / state.py:924 — here the buffer is
ALSO a post-mortem artifact).

Design constraints, in order:

- **Disabled path ~zero.** With ``task_event_sample_rate == 0`` (the
  default) every instrumentation site is ONE attribute load + branch
  (``if REC.enabled:``) — no dict building, no clock read.  Measured by
  ``overhead_probe()``.
- **kill -9 durable.** The ring is a memory-mapped file of fixed-size
  slots under ``<session>/events/``; every recorded span is already in
  the page cache when the process dies, so a SIGKILL'd worker's last
  moments are recoverable from disk (``recover_session``) with no exit
  handler ever running.  Open-span markers (``dur_us == -1``) written at
  exec *start* are what make a wedged/killed process debuggable: the
  post-mortem shows what it was doing, not just what it finished.
- **Bounded.** ``task_event_ring_slots`` fixed-size slots; the writer
  wraps and overwrites the oldest.  An oversized span drops its ``extra``
  payload rather than growing the slot (counted in ``clipped``).

Span record (ring + wire): a msgpack tuple
``(trace_id, span_id, parent_id, name, cat, ts_us, dur_us, extra|None)``.
Role/pid/node ride once per ring / per flush frame, not per span.
"""

from __future__ import annotations

import contextvars
import io
import itertools
import json
import mmap
import os
import random
import struct
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import msgpack

from ray_tpu._private.config import CONFIG

_MAGIC = b"RTFR"
_VERSION = 1
_HDR = struct.Struct("<4sHHII Q Q 24s 8s")  # 56 bytes used, pad to 64
_HDR_SIZE = 64
_COUNTER_OFF = 16
_CLIPPED_OFF = 24

# submit-side trace override: an orchestration layer (streaming shuffle,
# a sampled get) sets this so tasks it spawns join ITS trace tree instead
# of rolling independent sampling dice (contextvar: survives the
# main-thread → loop-thread hop only where we copy it explicitly, which
# is fine — submit_task reads it on the caller's thread)
_PARENT_CTX: contextvars.ContextVar[Optional[Tuple[int, int]]] = \
    contextvars.ContextVar("ray_tpu_trace_parent", default=None)
# executor-side current trace: set around user-code execution so in-task
# instrumentation (shard_pull in shuffle reduce bodies) can attach
_CUR_CTX: contextvars.ContextVar[Optional[Tuple[int, int]]] = \
    contextvars.ContextVar("ray_tpu_trace_current", default=None)


class SpanRecorder:
    """Process-wide flight recorder. ``enabled`` is False until
    :func:`configure` runs with a positive sample rate; every recording
    site guards on it, so the disabled path is one branch."""

    def __init__(self) -> None:
        self.enabled = False
        self.sample_rate = 0.0
        self.role = ""
        self.path: Optional[str] = None
        self._mm: Optional[mmap.mmap] = None
        self._ring_dir: Optional[str] = None
        self._nslots = 0
        self._slot = 0
        # RLock: recording sites are reachable from GC context (an
        # ObjectRef.__del__ cascading into task-failure bookkeeping that
        # records a span) — a plain Lock could deadlock against its own
        # thread mid-critical-section (raylint R1)
        self._mu = threading.RLock()
        self.counter = 0      # total records ever written
        self.clipped = 0      # spans whose extra payload was dropped
        self.flushed = 0      # records drained to the head so far
        self._id_base = 0
        self._id_seq = itertools.count(1)

    # ------------------------------------------------------------ lifecycle
    def configure(self, session_dir: str, role: str,
                  sample_rate: Optional[float] = None) -> bool:
        """Arm the recorder for this process. Reads
        ``task_event_sample_rate`` (env > cluster config > default) unless
        an explicit rate is passed; a rate of 0 leaves the recorder
        disabled and creates nothing on disk. Never raises — the
        observability plane must not take down what it observes."""
        rate = (float(CONFIG.task_event_sample_rate)
                if sample_rate is None else float(sample_rate))
        self.sample_rate = max(0.0, min(1.0, rate))
        self.role = role or self.role or "proc"
        if self.sample_rate <= 0.0:
            self.enabled = False
            return False
        try:
            if self._mm is None or self._ring_dir != session_dir:
                # re-init against a NEW session (init/shutdown/init in one
                # process) must not keep appending to the dead session's
                # ring; swap under the lock so a mid-record writer hits
                # either the old mmap (harmless) or the fresh one
                with self._mu:
                    old = self._mm
                    self._mm = None
                    self._open_ring(session_dir, self.role)
                    self.counter = self.flushed = self.clipped = 0
                if old is not None:
                    try:
                        old.close()
                    except Exception:
                        pass
            self.enabled = True
        except Exception:
            self.enabled = False
        return self.enabled

    def _open_ring(self, session_dir: str, role: str) -> None:
        nslots = max(64, int(CONFIG.task_event_ring_slots))
        slot = max(96, int(CONFIG.task_event_ring_slot_bytes))
        events_dir = os.path.join(session_dir or "/tmp", "events")
        os.makedirs(events_dir, exist_ok=True)
        self._ring_dir = session_dir
        self.path = os.path.join(events_dir, f"{role}-{os.getpid()}.ring")
        size = _HDR_SIZE + nslots * slot
        f = open(self.path, "w+b")
        try:
            f.truncate(size)
            self._mm = mmap.mmap(f.fileno(), size)
        finally:
            f.close()
        self._mm[:_HDR_SIZE] = _HDR.pack(
            _MAGIC, _VERSION, slot, nslots, os.getpid(), 0, 0,
            role.encode()[:24].ljust(24, b"\x00"), b"\x00" * 8
        ).ljust(_HDR_SIZE, b"\x00")
        self._nslots = nslots
        self._slot = slot
        self._id_base = int.from_bytes(os.urandom(6), "big") << 20
        self._id_seq = itertools.count(1)

    # ------------------------------------------------------------- identity
    def next_id(self) -> int:
        """Cheap process-unique 64-bit-ish id (random base + counter).
        Thread-safe without a lock: ids are minted from user threads,
        the IO loop and executor threads concurrently, and
        ``itertools.count.__next__`` is atomic under the GIL — a
        duplicated id would make the exporters' superseded-open-marker
        dedup swallow an unrelated span."""
        return (self._id_base + next(self._id_seq)) & 0x7FFFFFFFFFFFFFFF

    def sample(self) -> bool:
        """Root-site sampling decision (children inherit the parent's)."""
        if not self.enabled:
            return False
        r = self.sample_rate
        return r >= 1.0 or random.random() < r

    def new_trace(self) -> Tuple[int, int]:
        """(trace_id, root_span_id) for a freshly sampled root."""
        return self.next_id(), self.next_id()

    # ------------------------------------------------------------ recording
    def record(self, name: str, cat: str, ts: float, dur_s: float,
               trace_id: int, span_id: int, parent_id: int = 0,
               extra: Optional[Dict] = None) -> None:
        """Write one span. ``ts`` is epoch seconds, ``dur_s`` seconds
        (negative = open marker: the span BEGAN; closure, if any, is a
        later record with the same span_id). Thread-safe; never raises."""
        mm = self._mm
        if mm is None:
            return
        try:
            rec = msgpack.packb(
                (trace_id, span_id, parent_id, name, cat,
                 int(ts * 1e6), int(dur_s * 1e6) if dur_s >= 0 else -1,
                 extra),
                use_bin_type=True)
            limit = self._slot - 2
            if len(rec) > limit and extra is not None:
                rec = msgpack.packb(
                    (trace_id, span_id, parent_id, name, cat,
                     int(ts * 1e6), int(dur_s * 1e6) if dur_s >= 0 else -1,
                     None),
                    use_bin_type=True)
                with self._mu:
                    self.clipped += 1
                    mm[_CLIPPED_OFF:_CLIPPED_OFF + 8] = \
                        self.clipped.to_bytes(8, "little")
            if len(rec) > limit:
                return  # name alone exceeds the slot — drop the record
            with self._mu:
                idx = self.counter % self._nslots
                self.counter += 1
                off = _HDR_SIZE + idx * self._slot
                mm[off:off + 2] = len(rec).to_bytes(2, "little")
                mm[off + 2:off + 2 + len(rec)] = rec
                # counter last: a reader/recoverer never sees a slot the
                # header claims written but whose bytes are stale
                mm[_COUNTER_OFF:_COUNTER_OFF + 8] = \
                    self.counter.to_bytes(8, "little")
        except Exception:
            pass

    def open_marker(self, name: str, cat: str, trace_id: int, span_id: int,
                    parent_id: int = 0,
                    extra: Optional[Dict] = None) -> None:
        """Record that a span STARTED (post-mortem breadcrumb). The
        closing record shares the span_id; exporters keep the closed one."""
        self.record(name, cat, time.time(), -1.0, trace_id, span_id,
                    parent_id, extra)

    # -------------------------------------------------------------- reading
    def drain(self) -> List[tuple]:
        """Spans recorded since the last drain (bounded by ring capacity;
        overwritten-before-drained records count as dropped only in the
        sense that the ring bounds them — stats expose the gap)."""
        mm = self._mm
        if mm is None:
            return []
        out: List[tuple] = []
        with self._mu:
            start = max(self.flushed, self.counter - self._nslots)
            for i in range(start, self.counter):
                off = _HDR_SIZE + (i % self._nslots) * self._slot
                n = int.from_bytes(mm[off:off + 2], "little")
                if not (0 < n <= self._slot - 2):
                    continue
                try:
                    out.append(msgpack.unpackb(
                        bytes(mm[off + 2:off + 2 + n]), raw=False))
                except Exception:
                    continue
            self.flushed = self.counter
        return out

    def stats(self) -> Dict[str, int]:
        return {"recorded": self.counter, "clipped": self.clipped,
                "flushed": self.flushed}

    def dump_local(self, reason: str = "") -> Optional[str]:
        """Readable JSONL dump next to the ring — called from SIGTERM /
        fatal-exit / watchdog-wedge paths (kill -9 needs no dump: the
        ring file itself survives)."""
        if self.path is None:
            return None
        try:
            info = read_ring(self.path)
            out = self.path + ".dump.jsonl"
            with open(out, "w") as f:
                f.write(json.dumps({"reason": reason, "role": self.role,
                                    "pid": os.getpid(),
                                    "time": time.time(), **self.stats()})
                        + "\n")
                for sp in info.get("spans", []):
                    f.write(json.dumps(sp) + "\n")
            return out
        except Exception:
            return None


REC = SpanRecorder()


def configure(session_dir: str, role: str,
              sample_rate: Optional[float] = None) -> bool:
    return REC.configure(session_dir, role, sample_rate)


# ------------------------------------------------------------ trace context
def trace_parent(ctx: Optional[Tuple[int, int]]):
    """Context manager: tasks submitted inside join ``ctx``'s trace tree
    (used by the shuffle operator / sampled get); None is a no-op."""
    class _Tok:
        def __enter__(self):
            self._tok = _PARENT_CTX.set(ctx) if ctx is not None else None
            return self

        def __exit__(self, *exc):
            if self._tok is not None:
                _PARENT_CTX.reset(self._tok)

    return _Tok()


def parent_ctx() -> Optional[Tuple[int, int]]:
    return _PARENT_CTX.get()


def set_current(ctx: Optional[Tuple[int, int]]):
    return _CUR_CTX.set(ctx)


def reset_current(token) -> None:
    _CUR_CTX.reset(token)


def current_ctx() -> Optional[Tuple[int, int]]:
    """Executor-side: the trace context of the task currently running on
    this thread (None outside a sampled task)."""
    return _CUR_CTX.get()


# ------------------------------------------------------------- span helper
def sampled_root() -> Optional[Tuple[int, int]]:
    """``(trace_id, 0)`` for a root the armed recorder samples, else None:
    what a caller hands :class:`span` (``trace=``) for spans that share a
    trace and name no parent. One branch when the recorder is off."""
    return (REC.next_id(), 0) if REC.sample() else None


class span:
    """One interval of a host path, timed once and given to two outlets.

    ``with span(name, cat, extra, trace) as sp`` reads
    ``time.perf_counter()`` on the way in and out (``sp.t0``, ``sp.t1``:
    the caller's counters are sums of these, so a counter and its span are
    one interval) and

    - records into the ring as ``REC.record`` does (ids, parent,
      ``time.time()`` stamp) when the recorder is armed and the span
      belongs to a sampled trace: ``trace`` is the ``(trace_id, parent
      span id)`` to record under, and with none given the span is a child
      of the span or task that encloses it on this thread
      (``current_ctx()``), else nothing is recorded. ``extra`` may be set
      on ``sp`` until the block ends;
    - enters ``jax.profiler.TraceAnnotation("ray_tpu:" + name)`` when jax
      is ALREADY imported in this process (this module never imports it:
      a process without jax pays one dict lookup), so whenever anybody
      traces the process the span lies in the same ``.xplane.pb`` as the
      device's operations, on the profiler's clock. With no trace running
      an annotation is a check of one atomic.
    """

    __slots__ = ("name", "cat", "extra", "trace", "t0", "t1", "_ids",
                 "_ts", "_tok", "_ann")

    def __init__(self, name: str, cat: str, extra: Optional[Dict] = None,
                 trace: Optional[Tuple[int, int]] = None):
        self.name = name
        self.cat = cat
        self.extra = extra
        self.trace = trace
        self.t0 = self.t1 = 0.0
        self._ids = self._ann = None

    def __enter__(self) -> "span":
        if REC.enabled:
            parent = self.trace if self.trace is not None \
                else _CUR_CTX.get()
            if parent is not None:
                self._ids = (parent[0], REC.next_id(), parent[1])
                self._tok = _CUR_CTX.set(self._ids[:2])
                self._ts = time.time()
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            self._ann = profiler.TraceAnnotation("ray_tpu:" + self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._ids is not None:
            _CUR_CTX.reset(self._tok)
            REC.record(self.name, self.cat, self._ts, self.t1 - self.t0,
                       *self._ids, self.extra)
        return False


# ------------------------------------------------------------ start-up book
# One book a process of where its start-up went, filled by `startup_span`:
# seconds by phase (`startup.boot`, `startup.chip_bind`, `startup.construct`,
# `startup.import_jax`, `startup.devices`, `startup.weights`, `startup.warm`;
# a phase that runs again adds up), under `at` each phase's first
# ``time.time()`` start, and what the phases' owners note beside them
# (`actor_start`, `weights_ready_s`, `warm_s` by length). Read through
# `startup_stats()`; `LlamaGenerator.engine_stats()["startup"]` is this dict.
STARTUP: Dict[str, Any] = {"at": {}}
# (the ring the root was decided under, `sampled_root()` then)
_STARTUP_ROOT: List[Any] = [None, None]


def startup_stats() -> Dict[str, Any]:
    """The process's start-up book: a reference, not a copy."""
    return STARTUP


def startup_root() -> Optional[Tuple[int, int]]:
    """``(trace_id, 0)`` of the one trace this process's start-up spans
    share, decided once a ring by ``sampled_root()``; None with the
    recorder off (one branch) or a start-up it did not sample."""
    if not REC.enabled:
        return None
    if _STARTUP_ROOT[0] != REC.path:
        _STARTUP_ROOT[:] = [REC.path, sampled_root()]
    return _STARTUP_ROOT[1]


class startup_span(span):
    """A phase of this process's start-up: ``span("startup." + phase,
    "startup")`` whose seconds also land in the book. Armed, it is recorded
    under the process's start-up trace (``startup_root()``): a child of the
    start-up span that encloses it on this thread, else a root of that
    trace."""

    __slots__ = ("at",)

    def __init__(self, phase: str, extra: Optional[Dict] = None):
        root, cur = startup_root(), _CUR_CTX.get()
        inside = root is not None and cur is not None and cur[0] == root[0]
        super().__init__("startup." + phase, "startup", extra,
                         cur if inside else root)

    def __enter__(self) -> "startup_span":
        self.at = time.time()
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        STARTUP["at"].setdefault(self.name, self.at)
        STARTUP[self.name] = STARTUP.get(self.name, 0.0) + self.t1 - self.t0
        return False


def startup_record(name: str, at: float, dur_s: float,
                   extra: Optional[Dict] = None) -> None:
    """The ring's record of a start-up phase somebody else timed (one that
    ended before this process had a ring, or ends on another thread than
    it began on), a root of the start-up trace; nothing with the recorder
    off."""
    root = startup_root()
    if root is not None:
        REC.record(name, "startup", at, dur_s, root[0], REC.next_id(), 0,
                   extra)


class _FirstImport:
    """A ``sys.meta_path`` entry that times the first import of one module
    as a start-up phase, whoever imports it, and then takes itself out."""

    def __init__(self, module: str, phase: str):
        self.module, self.phase = module, phase

    def find_spec(self, name, path=None, target=None):
        if name != self.module:
            return None
        sys.meta_path.remove(self)
        import importlib.util

        spec = importlib.util.find_spec(name)
        if spec is not None and spec.loader is not None:
            run, phase = spec.loader.exec_module, self.phase

            def exec_module(module):
                with startup_span(phase):
                    run(module)

            spec.loader.exec_module = exec_module
        return spec


def time_first_import(module: str, phase: str) -> None:
    """Have the process's first ``import <module>`` be the start-up phase
    ``phase``, wherever it happens. Where the module is already imported
    the phase reads 0.0 unless it was timed; asked twice, it is timed
    once."""
    if module in sys.modules:
        STARTUP.setdefault("startup." + phase, 0.0)
    elif not any(isinstance(f, _FirstImport) and f.module == module
                 for f in sys.meta_path):
        sys.meta_path.insert(0, _FirstImport(module, phase))


# ------------------------------------------------------------ ring recovery
def _span_dict(tup, role: str = "", pid: int = 0,
               node_id: str = "") -> Dict[str, Any]:
    trace_id, span_id, parent_id, name, cat, ts_us, dur_us, extra = (
        list(tup) + [None] * 8)[:8]
    return {"trace": trace_id, "span": span_id, "parent": parent_id or 0,
            "name": name, "cat": cat, "ts_us": ts_us, "dur_us": dur_us,
            "extra": extra, "role": role, "pid": pid, "node": node_id}


def read_ring(path: str) -> Dict[str, Any]:
    """Parse one ring file from disk (a live process's or a dead one's).
    Returns {role, pid, recorded, clipped, spans: [span dicts]}."""
    with open(path, "rb") as f:
        data = f.read()
    if len(data) < _HDR_SIZE or data[:4] != _MAGIC:
        raise ValueError(f"not a flight-recorder ring: {path}")
    (_, _ver, slot, nslots, pid, counter, clipped, role_b, _pad
     ) = _HDR.unpack(data[:_HDR.size])
    role = role_b.rstrip(b"\x00").decode(errors="replace")
    spans: List[Dict] = []
    for i in range(max(0, counter - nslots), counter):
        off = _HDR_SIZE + (i % nslots) * slot
        n = int.from_bytes(data[off:off + 2], "little")
        if not (0 < n <= slot - 2):
            continue
        try:
            spans.append(_span_dict(
                msgpack.unpackb(data[off + 2:off + 2 + n], raw=False),
                role=role, pid=pid))
        except Exception:
            continue
    spans.sort(key=lambda s: s.get("ts_us") or 0)
    return {"role": role, "pid": pid, "recorded": counter,
            "clipped": clipped, "path": path, "spans": spans}


def recover_session(session_dir: str) -> List[Dict[str, Any]]:
    """All ring files of a session, parsed — THE post-mortem entry point
    after a chaos kill (``ray_tpu timeline --session <dir>`` rides it)."""
    events_dir = os.path.join(session_dir, "events")
    out: List[Dict] = []
    try:
        names = sorted(os.listdir(events_dir))
    except FileNotFoundError:
        return out
    for name in names:
        if not name.endswith(".ring"):
            continue
        try:
            out.append(read_ring(os.path.join(events_dir, name)))
        except Exception:
            continue
    return out


# -------------------------------------------------------- chrome-trace export
_ALLOWED_PH = {"X", "i", "M", "b", "e"}


def to_chrome_trace(spans: List[Dict[str, Any]],
                    task_events: Optional[List[Dict]] = None) -> List[Dict]:
    """Render span dicts (+ optional legacy task state events) as a valid
    Chrome-trace / Perfetto event list: ``M`` process metadata, nested
    ``X`` slices (tid = trace so concurrent tasks get their own lane and
    phases nest by containment), ``i`` instants for open markers and
    stray state events. Output is ts-sorted."""
    procs: Dict[tuple, int] = {}
    out: List[Dict] = []

    def pid_for(sp: Dict) -> int:
        key = (sp.get("node") or "", sp.get("role") or "", sp.get("pid") or 0)
        p = procs.get(key)
        if p is None:
            p = procs[key] = len(procs) + 1
            label = f"{key[1] or 'proc'} {key[0][:8]} pid={key[2]}"
            out.append({"ph": "M", "name": "process_name", "pid": p,
                        "tid": 0, "ts": 0,
                        "args": {"name": label.strip()}})
        return p

    # open markers whose span closed later are superseded by the close
    closed = {sp["span"] for sp in spans
              if (sp.get("dur_us") or -1) >= 0}
    for sp in spans:
        pid = pid_for(sp)
        tid = int(sp.get("trace") or 0) & 0xFFFFFF or 1
        args = {"trace": format(int(sp.get("trace") or 0), "x"),
                "span": format(int(sp.get("span") or 0), "x")}
        if sp.get("parent"):
            args["parent"] = format(int(sp["parent"]), "x")
        if sp.get("extra"):
            args.update({str(k): v for k, v in sp["extra"].items()})
        dur = sp.get("dur_us")
        if dur is None or dur < 0:
            if sp["span"] in closed:
                continue  # superseded open marker
            out.append({"ph": "i", "name": sp["name"], "cat": sp["cat"],
                        "ts": sp.get("ts_us") or 0, "pid": pid, "tid": tid,
                        "s": "t", "args": {**args, "open": True}})
        else:
            out.append({"ph": "X", "name": sp["name"], "cat": sp["cat"],
                        "ts": sp.get("ts_us") or 0, "dur": dur,
                        "pid": pid, "tid": tid, "args": args})
    node_pids: Dict[str, int] = {}

    def state_pid(nid: str) -> int:
        p = node_pids.get(nid)
        if p is None:
            p = node_pids[nid] = 1000 + len(node_pids)
            out.append({"ph": "M", "name": "process_name", "pid": p,
                        "tid": 0, "ts": 0,
                        "args": {"name": f"task states {nid[:8]}".strip()}})
        return p

    # legacy pairing (pre-recorder timeline behavior, kept so the default
    # sampling-off config still yields DURATION slices): PENDING/RETRYING
    # opens a task attempt, FINISHED/FAILED closes it as one X event
    open_start: Dict[str, Dict] = {}
    for e in sorted(task_events or [], key=lambda ev: ev.get("time") or 0):
        tid_hex = e.get("task_id") or ""
        state = e.get("state")
        tid = abs(hash(tid_hex)) % 0xFFFF or 1
        if state in ("PENDING", "RETRYING"):
            open_start[tid_hex] = e
            continue
        if state in ("FINISHED", "FAILED") and tid_hex in open_start:
            st = open_start.pop(tid_hex)
            out.append({
                "ph": "X", "name": str(e.get("name")), "cat": "task_state",
                "ts": (st.get("time") or 0) * 1e6,
                "dur": max(0.0, (e.get("time") or 0)
                           - (st.get("time") or 0)) * 1e6,
                "pid": state_pid(e.get("node_id") or ""), "tid": tid,
                "args": {"task_id": tid_hex, "state": state},
            })
            continue
        out.append({
            "ph": "i", "name": f"{e.get('name')}:{state}",
            "cat": "task_state", "ts": (e.get("time") or 0) * 1e6,
            "pid": state_pid(e.get("node_id") or ""), "tid": tid,
            "s": "t", "args": {"task_id": tid_hex, "state": state},
        })
    for tid_hex, st in open_start.items():  # still-running attempts
        out.append({
            "ph": "i", "name": f"{st.get('name')}:{st.get('state')}",
            "cat": "task_state", "ts": (st.get("time") or 0) * 1e6,
            "pid": state_pid(st.get("node_id") or ""),
            "tid": abs(hash(tid_hex)) % 0xFFFF or 1,
            "s": "t", "args": {"task_id": tid_hex,
                               "state": st.get("state"), "open": True},
        })
    out.sort(key=lambda ev: ev.get("ts", 0))
    return out


def format_trace_tree(spans: List[Dict[str, Any]]) -> str:
    """ASCII tree of one trace's spans (``ray_tpu trace <task_id>``)."""
    # same superseded-open-marker suppression as the chrome export: a
    # marker whose span closed later would render as a duplicate row
    closed = {sp["span"] for sp in spans if (sp.get("dur_us") or -1) >= 0}
    spans = [sp for sp in spans
             if (sp.get("dur_us") or -1) >= 0 or sp["span"] not in closed]
    if not spans:
        return "(no spans)"
    by_parent: Dict[int, List[Dict]] = {}
    ids = {sp["span"] for sp in spans}
    for sp in sorted(spans, key=lambda s: s.get("ts_us") or 0):
        parent = sp.get("parent") or 0
        by_parent.setdefault(parent if parent in ids else 0, []).append(sp)
    t0 = min(sp.get("ts_us") or 0 for sp in spans)
    buf = io.StringIO()

    def fmt(sp: Dict) -> str:
        dur = sp.get("dur_us")
        dur_s = "open" if (dur is None or dur < 0) else f"{dur / 1000:.2f}ms"
        where = f"{sp.get('role') or '?'}[{sp.get('node', '')[:8]}]"
        rel = ((sp.get("ts_us") or 0) - t0) / 1000
        return (f"{sp['name']}  +{rel:.2f}ms {dur_s}  {where}"
                f"  span={format(int(sp.get('span') or 0), 'x')}")

    seen = set()

    def walk(parent: int, depth: int) -> None:
        for sp in by_parent.get(parent, []):
            if id(sp) in seen:
                continue
            seen.add(id(sp))
            buf.write("  " * depth + ("- " if depth else "") + fmt(sp) + "\n")
            walk(sp["span"], depth + 1)

    walk(0, 0)
    for sp in sorted(spans, key=lambda s: s.get("ts_us") or 0):
        if id(sp) not in seen:  # orphaned parents (ring wrapped)
            buf.write("? " + fmt(sp) + "\n")
    return buf.getvalue().rstrip("\n")


def overhead_probe(n: int = 200_000) -> float:
    """ns/op of the DISABLED instrumentation guard — the branch every
    hot-path site pays when sampling is off
    (``tests/test_flight_recorder.py`` bounds it)."""
    probe = SpanRecorder()  # enabled=False, no ring
    t0 = time.perf_counter()
    for _ in range(n):
        if probe.enabled:  # the exact site shape
            probe.record("x", "x", 0.0, 0.0, 0, 0)
    took = time.perf_counter() - t0
    return took / n * 1e9
