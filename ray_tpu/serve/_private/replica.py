"""Replica actor (reference: python/ray/serve/_private/replica.py —
ReplicaActor :233, handle_request :391, queue-based admission control
``max_queued_requests`` + ``max_ongoing_requests``).

Hosts one instance of the user's deployment class/function. Admission is a
bounded queue: up to ``max_ongoing_requests`` execute concurrently, up to
``max_queued_requests`` more wait in FIFO order, and anything beyond that is
SHED with a typed reply the router surfaces as ``BackPressureError`` —
backpressure reaches the client as a fast typed error instead of the old
reject-and-spin retry loop. Every reply piggybacks the replica's current
queue depth so routers route on cached depths without probe RPCs.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import inspect
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ray_tpu._private import events

# admission-shed sentinel (kept under the old name too: external routers
# from this repo's earlier rounds knew it as REJECTED)
SHED = "__serve_shed__"
REJECTED = SHED


class AdmissionQueue:
    """Bounded FIFO admission shared by the async request path (actor
    event loop) and the sync streaming path (actor thread pool).

    ``acquire()`` returns ``None`` for immediate admission, a
    ``concurrent.futures.Future`` to wait on when queued (async callers
    ``wrap_future`` it — no thread is consumed while waiting), or raises
    ``_Shed`` when the queue is full or the replica is draining. Release
    hands the slot directly to the head waiter, preserving FIFO order.
    """

    def __init__(self, max_ongoing: int, max_queued: int):
        self.max_ongoing = max(1, int(max_ongoing))
        # max_queued < 0 means unbounded (reference default); 0 disables
        # queueing entirely (round-5 reject semantics, typed now)
        self.max_queued = int(max_queued)
        self._lock = threading.Lock()
        self._ongoing = 0
        self._waiters: list = []  # FIFO of Futures
        self.shed_total = 0

    class _Shed(Exception):
        pass

    @property
    def ongoing(self) -> int:
        return self._ongoing

    @property
    def queued(self) -> int:
        return len(self._waiters)

    @property
    def depth(self) -> int:
        """Total demand parked on this replica: running + queued."""
        with self._lock:
            return self._ongoing + len(self._waiters)

    def acquire(self, draining: bool = False):
        with self._lock:
            if draining:
                self.shed_total += 1
                raise self._Shed()
            if self._ongoing < self.max_ongoing and not self._waiters:
                self._ongoing += 1
                return None
            if self.max_queued >= 0 and len(self._waiters) >= self.max_queued:
                self.shed_total += 1
                raise self._Shed()
            fut: "concurrent.futures.Future" = concurrent.futures.Future()
            self._waiters.append(fut)
            return fut

    def release(self) -> None:
        with self._lock:
            # hand-off: the slot passes to the head waiter without the
            # ongoing count ever dipping (no thundering herd, strict FIFO)
            while self._waiters:
                fut = self._waiters.pop(0)
                if fut.set_running_or_notify_cancel():
                    fut.set_result(None)
                    return
            self._ongoing -= 1

    def abandon(self, fut) -> None:
        """A queued waiter gave up (cancelled/timed out upstream)."""
        with self._lock:
            try:
                self._waiters.remove(fut)
            except ValueError:
                pass

    def note_shed(self) -> None:
        """Count a shed decided outside acquire (e.g. TTL expiry)."""
        with self._lock:
            self.shed_total += 1


class _HandlePlaceholder:
    """Marks a bound sub-deployment in init args; resolved to a
    DeploymentHandle inside the replica."""

    def __init__(self, app_name: str, dep_name: str):
        self.app_name = app_name
        self.dep_name = dep_name


class Replica:
    def __init__(self, blob: bytes, init_blob: bytes, app_name: str,
                 dep_name: str, max_ongoing_requests: int,
                 user_config: Any, max_queued_requests: int = 64):
        import cloudpickle

        self._app_name = app_name
        self._dep_name = dep_name
        self._admission = AdmissionQueue(max_ongoing_requests,
                                         max_queued_requests)
        self._draining = False

        func_or_class = cloudpickle.loads(blob)
        args, kwargs = cloudpickle.loads(init_blob)
        args = tuple(self._resolve_deep(a) for a in args)
        kwargs = {k: self._resolve_deep(v) for k, v in kwargs.items()}

        # the user's callable whole: its constructor and its first
        # `reconfigure`
        with events.startup_span("construct", {"deployment": dep_name}):
            if isinstance(func_or_class, type):
                self._callable = func_or_class(*args, **kwargs)
                self._is_function = False
            else:
                self._callable = func_or_class
                self._is_function = True
            if user_config is not None:
                self._apply_user_config(user_config)

    @staticmethod
    def _resolve(arg):
        if isinstance(arg, _HandlePlaceholder):
            from ray_tpu.serve.handle import DeploymentHandle

            return DeploymentHandle(arg.app_name, arg.dep_name)
        return arg

    @classmethod
    def _resolve_deep(cls, arg):
        """Placeholders can sit inside graph nodes / containers
        (deployment-graph init args), not just at the top level."""
        from ray_tpu.serve.deployment import map_graph_values

        return map_graph_values(arg, cls._resolve)

    def _apply_user_config(self, cfg):
        fn = getattr(self._callable, "reconfigure", None)
        if fn is not None:
            fn(cfg)

    # ------------------------------------------------------------- control
    def ready(self) -> bool:
        return True

    def health_check(self) -> Dict[str, int]:
        """Health probe + serving metrics in one RPC: the controller's
        autoscaler consumes queue depth and shed totals, not just ongoing
        counts (reference: replica queue-len metrics pushed to the
        controller for autoscaling_policy.py)."""
        check = getattr(self._callable, "check_health", None)
        if check is not None:
            check()
        eng = getattr(self._callable, "engine", None)
        stats = {}
        try:
            from ray_tpu.serve._private.engine import ContinuousBatchingEngine

            if isinstance(eng, ContinuousBatchingEngine):
                stats = eng.stats()
        except Exception:
            stats = {}
        return {
            "ongoing": self._admission.ongoing,
            "queued": self._admission.queued,
            "depth": self._admission.ongoing + self._admission.queued,
            "shed_total": self._admission.shed_total
            + int(stats.get("shed", 0)),
            "engine_steps": int(stats.get("steps", 0)),
        }

    def get_queue_len(self) -> int:
        return self._admission.depth

    def reconfigure(self, user_config) -> bool:
        self._apply_user_config(user_config)
        return True

    async def drain(self) -> bool:
        """Stop admitting (new requests shed), let running AND queued
        requests finish, then stop any batching engine the user callable
        owns — the controller's scale-down path awaits this before kill."""
        self._draining = True
        while self._admission.depth > 0:
            await asyncio.sleep(0.02)
        eng = getattr(self._callable, "engine", None)
        if eng is not None and hasattr(eng, "shutdown"):
            try:
                await asyncio.to_thread(eng.shutdown)
            except Exception:
                pass
        return True

    def _target(self, method_name: Optional[str]):
        if self._is_function:
            return self._callable
        return getattr(self._callable, method_name or "__call__")

    def _shed_reply(self) -> Tuple:
        return (SHED, None, self._admission.depth)

    # ------------------------------------------------------------- requests
    async def handle_request(self, method_name: Optional[str], args: Tuple,
                             kwargs: Dict, multiplexed_model_id: str = "",
                             ttl: Optional[float] = None):
        target = self._target(method_name)
        if inspect.isgeneratorfunction(target) or \
                inspect.isasyncgenfunction(target):
            # generator endpoint: the caller must re-issue through the
            # streaming path (checked BEFORE admission, so the slot is
            # taken once, by the streaming call that does the work)
            return ("stream", None, self._admission.depth)
        t0 = time.monotonic()
        try:
            ticket = self._admission.acquire(self._draining)
        except AdmissionQueue._Shed:
            return self._shed_reply()
        if isinstance(ticket, concurrent.futures.Future):
            # queued: await admission without holding a thread
            try:
                await asyncio.wrap_future(ticket)
            except asyncio.CancelledError:
                # raced an in-flight hand-off: if the slot was already
                # granted, give it back, else just leave the queue
                if ticket.done() and not ticket.cancelled():
                    self._admission.release()
                else:
                    self._admission.abandon(ticket)
                raise
            if ttl is not None and time.monotonic() - t0 > ttl:
                # the caller's deadline passed while we were queued: the
                # client already saw TimeoutError (and may have retried) —
                # running user code now would double side effects
                self._admission.release()
                self._admission.note_shed()
                return self._shed_reply()
        try:
            from ray_tpu.serve import multiplex

            if multiplexed_model_id:
                multiplex._set_request_model_id(multiplexed_model_id)
            if inspect.iscoroutinefunction(target):
                result = await target(*args, **kwargs)
            else:
                # sync user code runs off-loop so concurrent requests (and
                # the admission check) aren't serialized behind it
                result = await asyncio.to_thread(target, *args, **kwargs)
                if inspect.iscoroutine(result):
                    result = await result
            from ray_tpu.serve.asgi import StreamingResponse, iterate_sync

            if isinstance(result, StreamingResponse) or \
                    inspect.isgenerator(result):
                # lazily-built stream object: drain it OFF-LOOP (this
                # coroutine runs on the replica's event loop; a sync drain
                # would stall concurrent requests, and iterate_sync spins a
                # private loop for async iterables which must not nest in a
                # running one). Bounded by the handle's 60s request budget;
                # declare the endpoint as a generator function for true
                # incremental streaming.
                if isinstance(result, StreamingResponse):
                    chunks = await asyncio.to_thread(
                        lambda: list(iterate_sync(result.content)))
                    return ("stream_buffered",
                            {"chunks": chunks,
                             "status_code": result.status_code,
                             "media_type": result.media_type,
                             "headers": result.headers},
                            self._admission.depth)
                chunks = await asyncio.to_thread(lambda: list(result))
                return ("stream_buffered",
                        {"chunks": chunks, "status_code": 200,
                         "media_type": "application/octet-stream",
                         "headers": {}}, self._admission.depth)
            return ("ok", result, self._admission.depth)
        finally:
            self._admission.release()
            if multiplexed_model_id:
                multiplex._set_request_model_id("")

    def handle_request_streaming(self, method_name: Optional[str],
                                 args: Tuple, kwargs: Dict,
                                 multiplexed_model_id: str = "",
                                 ttl: Optional[float] = None):
        """Streaming execution path (reference: replica.py:471): a sync
        generator method — called with num_returns='streaming', each yield
        becomes an ObjectRef at the caller as it is produced. First item is
        the admission handshake. Runs in the actor's thread pool, so a
        queued request blocks its pool thread (the controller sizes
        max_concurrency for max_ongoing + max_queued + headroom)."""
        t0 = time.monotonic()
        try:
            ticket = self._admission.acquire(self._draining)
        except AdmissionQueue._Shed:
            yield self._shed_reply()
            return
        if isinstance(ticket, concurrent.futures.Future):
            try:
                ticket.result()
            except BaseException:
                if ticket.done() and not ticket.cancelled():
                    self._admission.release()
                else:
                    self._admission.abandon(ticket)
                raise
            if ttl is not None and time.monotonic() - t0 > ttl:
                self._admission.release()
                self._admission.note_shed()
                yield self._shed_reply()
                return
        try:
            from ray_tpu.serve import multiplex
            from ray_tpu.serve.asgi import StreamingResponse, iterate_sync

            if multiplexed_model_id:
                multiplex._set_request_model_id(multiplexed_model_id)
            target = self._target(method_name)
            if inspect.isasyncgenfunction(target):
                result = target(*args, **kwargs)
            elif inspect.iscoroutinefunction(target):
                result = asyncio.run(target(*args, **kwargs))
            else:
                result = target(*args, **kwargs)
            depth = self._admission.ongoing + self._admission.queued
            if isinstance(result, StreamingResponse):
                yield ("start", {"status_code": result.status_code,
                                 "media_type": result.media_type,
                                 "headers": result.headers,
                                 "queue_depth": depth})
                for chunk in iterate_sync(result.content):
                    yield ("chunk", chunk)
            elif inspect.isgenerator(result) or hasattr(result, "__aiter__"):
                yield ("start", {"status_code": 200,
                                 "media_type": "application/octet-stream",
                                 "headers": {},
                                 "queue_depth": depth})
                for chunk in iterate_sync(result):
                    yield ("chunk", chunk)
            else:
                # non-streaming endpoint called through the streaming path:
                # a single-chunk stream
                yield ("start", {"status_code": 200, "media_type": None,
                                 "headers": {}, "queue_depth": depth})
                yield ("chunk", result)
        finally:
            self._admission.release()
            if multiplexed_model_id:
                multiplex._set_request_model_id("")
