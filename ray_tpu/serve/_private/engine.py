"""Continuous (iteration-level) batching engine for generative serving.

The static ``@serve.batch`` path batches whole requests: a batch forms,
runs to completion, and every slot is held hostage by the longest
generation in it. For token-by-token generation the standard production
shape is *continuous batching* (reference: vLLM / Ray Serve LLM
deployments; PAPER.md layer 11): the scheduler operates at STEP
granularity — each iteration advances every in-flight generation by one
step, finished requests leave the batch at the step boundary, and waiting
requests join at the next one. Short generations never wait for long
ones, and the hardware batch stays full under mixed-length load.

TPU deviations from the GPU-shaped reference:

- **Bucketed batch composition.** Jitted models compile per input shape,
  so the per-step batch is padded with ``None`` slots up to the smallest
  ``allowed_batch_sizes`` bucket that fits — the user's ``step_fn`` sees
  a fixed menu of batch widths and compiles once per bucket, exactly like
  ``@serve.batch``'s shape bucketing but applied every iteration.
- **Per-adapter grouping.** Multiplexed (LoRA-adapter) requests are
  grouped by model id: each step runs one adapter group, rotated
  round-robin, so a step applies a single adapter pytree to the whole
  batch instead of gathering per-row adapters.

The engine owns one background *stepper* thread. It is started lazily on
the first submit and EXITS when the engine sits idle (no running or
pending requests) for ``idle_timeout_s`` — an idle engine leaves no
daemon behind, which keeps the test suite's leak gate meaningful and
``serve.shutdown()`` clean. ``Replica.drain`` calls ``shutdown()``
explicitly before a scale-down kill.

User contract::

    def step_fn(model_id, states):  # states: List[Optional[state]]
        # padded to an allowed bucket with None; advance every real
        # state one iteration and return a same-length list whose real
        # slots are (emit, done) — emit is streamed to the caller
        # (skipped when None), done=True removes it from the batch.
        ...

    engine = ContinuousBatchingEngine(step_fn, max_batch_size=8,
                                      allowed_batch_sizes=(2, 4, 8))
    for token in engine.submit(payload, model_id="adapter-1"):
        ...
"""

from __future__ import annotations

import collections
import queue
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence

from ray_tpu._private import events
from ray_tpu.exceptions import BackPressureError

_DONE = object()

# every live engine, for the leak gate: a stepper thread that outlives its
# workload (or the suite) is a bug the conftest session gate fails on
_live_engines: "weakref.WeakSet" = weakref.WeakSet()


def live_stepper_threads() -> List[str]:
    """Names of stepper threads still alive across all live engines."""
    out = []
    for eng in list(_live_engines):
        t = eng._thread
        if t is not None and t.is_alive():
            out.append(t.name)
    return out


class _EngineError:
    """Exception envelope on a request's output queue."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class _Request:
    __slots__ = ("payload", "model_id", "state", "out", "cancelled",
                 "queued_at", "joined_at", "emitted", "trace")

    def __init__(self, payload: Any, model_id: str):
        self.payload = payload
        self.model_id = model_id
        self.state: Any = None
        self.out: "queue.SimpleQueue" = queue.SimpleQueue()
        self.cancelled = False
        # `time.perf_counter()` on entering `_pending` and on getting a row
        self.queued_at = 0.0
        self.joined_at = 0.0
        self.emitted = 0
        # (trace_id, 0) when the flight recorder samples this request
        self.trace = events.sampled_root()


def _request_span(name: str, req: _Request, dur_s: float) -> None:
    """A sampled request's span, ended now. The ring's alone: it starts on
    the caller's thread and ends on the stepper's, which no
    ``TraceAnnotation`` can. A state that says its ``prompt_len`` (as
    ``LlamaGenerator``'s does) has it in the extra."""
    extra = {"emitted": req.emitted}
    if isinstance(req.state, dict) and "prompt_len" in req.state:
        extra["prompt_len"] = req.state["prompt_len"]
    events.REC.record(name, "serve", time.time() - dur_s, dur_s,
                      req.trace[0], events.REC.next_id(), 0, extra)


class ContinuousBatchingEngine:
    def __init__(self, step_fn: Callable[[str, List], List], *,
                 max_batch_size: int = 8,
                 allowed_batch_sizes: Optional[Sequence[int]] = None,
                 prefill_fn: Optional[Callable[[Any, str], Any]] = None,
                 max_pending: Optional[int] = None,
                 idle_timeout_s: float = 0.5,
                 name: str = "engine"):
        self.step_fn = step_fn
        self.prefill_fn = prefill_fn
        self.allowed = (sorted(set(int(a) for a in allowed_batch_sizes))
                        if allowed_batch_sizes else None)
        self.max_batch_size = int(max_batch_size)
        if self.allowed:
            # the largest bucket caps the batch; buckets above the cap
            # would never dispatch
            self.allowed = [a for a in self.allowed
                            if a <= self.max_batch_size] or [
                                self.max_batch_size]
            self.max_batch_size = self.allowed[-1]
        self.max_pending = max_pending
        self.idle_timeout_s = idle_timeout_s
        self.name = name

        self._lock = threading.Lock()
        self._pending: "collections.deque[_Request]" = collections.deque()
        self._groups: Dict[str, List[_Request]] = {}
        self._rr: "collections.deque[str]" = collections.deque()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stopped = False

        # counters (exposed via stats(); the replica folds them into its
        # health probe so the controller/bench see engine behavior).
        # Counts: steps, emitted, completed, shed, the widest batch, padded
        # slots, `joined` (requests that got a row). Seconds, each a sum of
        # `time.perf_counter()` intervals that are also spans
        # (`events.span`): `active_s`, whole iterations from
        # `engine.admit` through `engine.step_fn` to the end of
        # `engine.emit` (what it leaves of a wall time is an engine with
        # nothing to run); `queue_wait_s` and `queue_wait_max_s`, the sum
        # and the longest of the waits in `_pending` of the requests that
        # joined (`request.queue`). The stepper alone writes them (`shed`
        # apart: `submit` does, under the lock); `stats()` reads them
        # under the lock.
        self._steps = 0
        self._emitted = 0
        self._completed = 0
        self._shed = 0
        self._max_batch_seen = 0
        self._padded_slots = 0
        self._joined = 0
        self._active_s = 0.0
        self._queue_wait_s = 0.0
        self._queue_wait_max_s = 0.0
        _live_engines.add(self)

    # ---------------------------------------------------------------- public
    def bucket_for(self, n: int) -> int:
        """Smallest allowed batch size that fits n live requests."""
        if not self.allowed:
            return n
        for a in self.allowed:
            if a >= n:
                return a
        return self.allowed[-1]

    def submit(self, payload: Any, model_id: str = ""):
        """Enqueue one generation; returns a sync iterator of emitted
        items. Sheds with ``BackPressureError`` beyond ``max_pending``
        (the serve replica's admission queue is the usual bound — this
        cap protects direct/standalone engine users)."""
        req = _Request(payload, model_id)
        with self._lock:
            if self._stopped:
                raise RuntimeError(f"{self.name}: engine is shut down")
            if self.max_pending is not None:
                depth = len(self._pending) + sum(
                    len(g) for g in self._groups.values())
                if depth >= self.max_pending:
                    self._shed += 1
                    raise BackPressureError(
                        deployment=self.name,
                        queue_depths={self.name: depth})
        # prefill OUTSIDE the lock (and off the stepper thread): a
        # jit-compiling / forward-pass prefill must not block concurrent
        # submit()/stats()/shutdown() — stats() feeds the replica health
        # probe, and a multi-second stall there reads as "unhealthy"
        try:
            req.state = (self.prefill_fn(req.payload, req.model_id)
                         if self.prefill_fn is not None else req.payload)
        except BaseException as e:  # noqa: BLE001 — user prefill code
            req.out.put(_EngineError(e))
            return self._consume(req)
        with self._lock:
            if self._stopped:
                raise RuntimeError(f"{self.name}: engine is shut down")
            req.queued_at = time.perf_counter()
            self._pending.append(req)
            self._ensure_thread_locked()
        self._wake.set()
        return self._consume(req)

    def stats(self) -> Dict[str, float]:
        """The counters, a flat dict of numbers (the replica's health
        probe calls this)."""
        with self._lock:
            running = sum(len(g) for g in self._groups.values())
            return {
                "steps": self._steps, "emitted": self._emitted,
                "completed": self._completed, "shed": self._shed,
                "running": running, "pending": len(self._pending),
                "max_batch": self._max_batch_seen,
                "padded_slots": self._padded_slots,
                "joined": self._joined, "active_s": self._active_s,
                "queue_wait_s": self._queue_wait_s,
                "queue_wait_max_s": self._queue_wait_max_s,
            }

    def shutdown(self, timeout: float = 5.0) -> None:
        """Stop the stepper and fail all in-flight requests. Idempotent."""
        with self._lock:
            self._stopped = True
            doomed = list(self._pending)
            self._pending.clear()
            for g in self._groups.values():
                doomed.extend(g)
            self._groups.clear()
            self._rr.clear()
            t = self._thread
        self._wake.set()
        err = RuntimeError(f"{self.name}: engine shut down mid-generation")
        for req in doomed:
            req.out.put(_EngineError(err))
        if t is not None and t.is_alive() and \
                t is not threading.current_thread():
            t.join(timeout)

    # --------------------------------------------------------------- consume
    def _consume(self, req: _Request):
        def gen():
            try:
                while True:
                    item = req.out.get()
                    if item is _DONE:
                        return
                    if isinstance(item, _EngineError):
                        raise item.exc
                    yield item
            finally:
                # consumer went away (close/GC/exception): leave the
                # batch at the next step boundary instead of generating
                # tokens nobody reads
                req.cancelled = True

        return gen()

    # --------------------------------------------------------------- stepper
    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name=f"serve-engine-{self.name}")
            self._thread.start()

    def _run(self) -> None:
        while True:
            with self._lock:
                if self._stopped:
                    return
                idle = not self._pending and not any(self._groups.values())
                if idle:
                    # nothing to do: wait for work, exit when idle past
                    # the timeout (restarted lazily by the next submit)
                    self._wake.clear()
            if not idle:
                self._iteration()
            elif not self._wake.wait(self.idle_timeout_s):
                with self._lock:
                    if not self._pending and not any(
                            self._groups.values()) \
                            and self._thread is \
                            threading.current_thread():
                        self._thread = None
                        return

    def _admit_locked(self) -> None:
        """Join waiting requests at the step boundary, FIFO, capped by the
        per-group batch width."""
        skipped: List[_Request] = []
        now = time.perf_counter()
        while self._pending:
            req = self._pending.popleft()
            if req.cancelled:
                continue
            group = self._groups.get(req.model_id)
            if group is None:
                group = self._groups[req.model_id] = []
                self._rr.append(req.model_id)
            if len(group) >= self.max_batch_size:
                skipped.append(req)  # group full: wait for a leave
                continue
            wait = now - req.queued_at
            req.joined_at = now
            self._joined += 1
            self._queue_wait_s += wait
            self._queue_wait_max_s = max(self._queue_wait_max_s, wait)
            if req.trace is not None:
                _request_span("request.queue", req, wait)
            group.append(req)
        self._pending.extendleft(reversed(skipped))

    def _select_locked(self):
        """Next adapter group, round-robin; drops empty groups."""
        for _ in range(len(self._rr)):
            if not self._rr:
                break
            mid = self._rr[0]
            self._rr.rotate(-1)
            group = self._groups.get(mid)
            if group:
                live = [r for r in group if not r.cancelled]
                if len(live) != len(group):
                    self._groups[mid] = live
                    self._left([r for r in group if r.cancelled])
                if live:
                    return mid, list(live[:self.max_batch_size])
            if not self._groups.get(mid):
                self._groups.pop(mid, None)
                try:
                    self._rr.remove(mid)
                except ValueError:
                    pass
        return None, None

    def _left(self, reqs: List[_Request]) -> None:
        """The requests that gave their rows back (done, cancelled or
        failed): a sampled one's ``request.generate`` ends here."""
        if not events.REC.enabled:
            return
        now = time.perf_counter()
        for r in reqs:
            if r.trace is not None:
                _request_span("request.generate", r, now - r.joined_at)

    def _iteration(self) -> None:
        """One pass of the stepper, as three sibling spans that tile it:
        ``engine.admit`` (the lock, the joins, the choice of a group,
        padding it to its bucket), ``engine.step_fn`` (the call, whatever
        the function) and ``engine.emit`` (results to the requests'
        queues, the finished retired). A pass that finds no batch (every
        waiting request had been cancelled) ends in ``engine.admit``."""
        trace = events.sampled_root()
        with events.span("engine.admit", "serve", trace=trace) as admit:
            with self._lock:
                self._admit_locked()
                model_id, batch = self._select_locked()
                pending = len(self._pending)
            if batch is None:
                return
            states: List[Optional[Any]] = [r.state for r in batch]
            bucket = self.bucket_for(len(states))
            pad = bucket - len(states)
            if pad > 0:
                states = states + [None] * pad
            admit.extra = extra = {"rows": len(batch), "bucket": bucket,
                                   "pad": pad, "pending": pending}
        failure: Optional[BaseException] = None
        results = None
        with events.span("engine.step_fn", "serve", extra, trace):
            try:
                results = self.step_fn(model_id, states)
            except BaseException as e:  # noqa: BLE001 — user step code
                failure = e
        with events.span("engine.emit", "serve", extra, trace) as emit:
            finished = list(batch) if failure is not None \
                else self._emit(batch, results, bucket, pad)
            if finished:
                with self._lock:
                    group = self._groups.get(model_id, [])
                    for r in finished:
                        try:
                            group.remove(r)
                        except ValueError:
                            pass
                self._left(finished)
                for r in finished:
                    if failure is not None:
                        r.out.put(_EngineError(failure))
                    else:
                        r.out.put(_DONE)
                        self._completed += 1
        self._active_s += emit.t1 - admit.t0

    def _emit(self, batch: List[_Request], results, bucket: int,
              pad: int) -> List[_Request]:
        """A step's results to the requests' queues; returns the requests
        that are done."""
        self._steps += 1
        self._max_batch_seen = max(self._max_batch_seen, len(batch))
        self._padded_slots += pad
        if results is None or len(results) < len(batch):
            err = ValueError(
                f"{self.name}: step_fn returned "
                f"{0 if results is None else len(results)} results for a "
                f"bucket of {bucket} ({len(batch)} live)")
            for r in batch:
                r.out.put(_EngineError(err))
            return list(batch)
        finished = []
        for r, res in zip(batch, results):
            emit, done = (None, False) if res is None else res
            if emit is not None and not r.cancelled:
                r.out.put(emit)
                r.emitted += 1
                self._emitted += 1
            if done:
                finished.append(r)
        return finished
