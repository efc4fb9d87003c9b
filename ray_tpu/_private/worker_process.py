"""Worker process entrypoint + task executor.

Parity with the reference's worker-side execution path (reference:
``python/ray/_raylet.pyx:1647`` execute_task +
``src/ray/core_worker/transport/`` scheduling queues): the worker registers
with its node agent, listens for direct PushTask RPCs from owners, executes
normal tasks serially, orders actor tasks per-caller by sequence number
(ActorSchedulingQueue analog), runs async actor methods on the event loop with
a concurrency cap, and writes large returns straight to the node's shm store.
"""

from __future__ import annotations

import asyncio
import functools
import inspect
import os
import sys
import threading
import time
import traceback
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

from ray_tpu._private import events as _events
from ray_tpu._private import serialization as ser
from ray_tpu._private.config import CONFIG
from ray_tpu._private.function_table import load_function
from ray_tpu._private.ids import ActorID, JobID, ObjectID, TaskID
from ray_tpu._private.object_ref import ObjectRef, _rebuild_ref
from ray_tpu._private.task_spec import ACTOR_TASK, NORMAL_TASK, TaskSpec
from ray_tpu._private.worker import EXC, VAL, Worker
from ray_tpu.exceptions import RayTaskError


def _seed_task_rng(seed: int) -> None:
    """Seed the task body's RNGs for deterministic lineage replay
    (ISSUE 17). Only seeds libraries the process ALREADY imported —
    replay must not warm numpy/jax in otherwise-light map/reduce
    workers."""
    import random as _random

    _random.seed(seed)
    np = sys.modules.get("numpy")
    if np is not None:
        try:
            np.random.seed(seed & 0xFFFFFFFF)
        except Exception:
            pass


class Executor:
    def __init__(self, worker: Worker):
        self.worker = worker
        self._task_pool = ThreadPoolExecutor(max_workers=1,
                                             thread_name_prefix="task-exec")
        self._actor_pool: Optional[ThreadPoolExecutor] = None
        self._actor_sem: Optional[asyncio.Semaphore] = None
        # Async actor methods run on a DEDICATED event loop thread, not the
        # worker's IO loop: user coroutines may make blocking ray_tpu calls
        # (get/remote/get_actor), which round-trip through the IO loop and
        # would deadlock it (reference keeps async actors on fibers separate
        # from the core-worker io_service for the same reason, fiber.h).
        self._actor_loop: Optional[asyncio.AbstractEventLoop] = None
        self._actor_cls = None
        self._actor_id: Optional[ActorID] = None
        self._max_concurrency = 1
        self._actor_has_async = False
        # Per-caller-connection execution chains. TCP delivers one caller's
        # pushes in submission order; chaining on the connection preserves
        # that order through execution and is naturally restart-safe (a
        # reconnecting caller starts a fresh chain) — the role the seq-based
        # ActorSchedulingQueue plays in the reference.
        self._chain_tail: Dict[int, asyncio.Future] = {}
        # Batched execution drainer: queued specs run FIFO on one pool thread
        # and results post back through a coalesced doorbell, so a burst of
        # pipelined pushes costs two thread handoffs total instead of two per
        # task (reference keeps this loop in C++; see scheduling queues in
        # src/ray/core_worker/transport/).
        self._exec_mu = threading.Lock()
        self._exec_queue: deque = deque()
        self._drainer_active = False
        self._res_mu = threading.Lock()
        self._results: List = []
        self._res_armed = False

    # ------------------------------------------------------------- dispatch
    async def handle_push_task(self, conn, wire: Dict) -> Dict:
        if not self.worker.ready_event.is_set():
            await self.worker.ready_event.wait()
        spec = TaskSpec.from_wire(wire)  # tolerates extra frame keys
        assigned = wire.get("assigned_instances") or {}
        start = time.monotonic()
        if spec.task_type == ACTOR_TASK and self._max_concurrency == 1:
            if self._actor_has_async:
                # chain per caller so sync and async methods stay ordered
                reply = await self._ordered_actor_task(conn, spec)
            else:
                reply = await self._run_on_drainer(spec, {})
        elif spec.task_type == ACTOR_TASK:
            reply = await self._execute_async(spec, assigned)
        else:
            reply = await self._run_on_drainer(spec, assigned)
        # Execution duration feeds the owner's adaptive pipelining (short
        # tasks pipeline deep to amortize wakeups; long tasks stay shallow).
        if isinstance(reply, dict) and "exec_ms" not in reply:
            reply["exec_ms"] = (time.monotonic() - start) * 1000.0
        if _events.REC.enabled:
            self.worker._maybe_flush_spans()
        return reply

    async def handle_push_task_batch_stream(self, conn, p: Dict) -> Dict:
        """One frame, many pushes — but each item's result STREAMS back as
        a BatchItem push the moment it completes (write-combined), so a
        fast item's caller isn't gated on a slow sibling and a dependent
        task batched behind its producer sees the producer's result
        immediately. The frame's reply just closes the batch (reference:
        the per-task PushTask replies of direct_actor_task_submitter.h,
        amortized onto one submission frame)."""
        bid = p["b"]
        wires = p["specs"]
        ai = p.get("ai")
        if ai:
            # batch-level accelerator assignment (ISSUE 18): identical for
            # every item on one leased worker, so it rides the frame once
            # instead of being copied into each spec by the submitter
            for w in wires:
                w.setdefault("assigned_instances", ai)
        # items completing in the same loop tick coalesce into ONE frame
        # (a serial run of sub-ms tasks streams as a few chunky pushes; a
        # slow task's result still leaves the moment it lands)
        out: List = []
        armed = [False]

        def flush() -> None:
            armed[0] = False
            if out:
                items, out[:] = list(out), []
                try:
                    conn.push_nowait("BatchItems", {"b": bid, "xs": items})
                except Exception:
                    pass  # owner gone; the final reply will fail too

        # drainer fast lane (ISSUE 18): a frame whose items all execute on
        # the serial drainer — normal tasks, or sync methods of a
        # concurrency-1 actor — lands in the exec queue under ONE lock
        # with plain future callbacks, instead of a coroutine + per-item
        # enqueue per task. Async/concurrent actors keep the general path
        # (their ordering runs through chains/semaphores, not the queue).
        if len(wires) > 1 and not self._actor_has_async \
                and self._max_concurrency == 1:
            if not self.worker.ready_event.is_set():
                await self.worker.ready_event.wait()
            loop = asyncio.get_running_loop()
            futs: List[asyncio.Future] = []
            with self._exec_mu:
                for w in wires:
                    fut = loop.create_future()
                    self._exec_queue.append(
                        (TaskSpec.from_wire(w),
                         w.get("assigned_instances") or {}, fut, loop))
                    futs.append(fut)
                start_drainer = not self._drainer_active
                if start_drainer:
                    self._drainer_active = True
            if start_drainer:
                pool = (self._actor_pool if self._actor_pool is not None
                        else self._task_pool)
                pool.submit(self._drain_exec)

            def on_done(i: int, fut: "asyncio.Future") -> None:
                e = fut.exception()
                out.append((i, {"batch_item_error": repr(e)}
                            if e is not None else fut.result()))
                if not armed[0]:
                    armed[0] = True
                    loop.call_soon(flush)

            for i, fut in enumerate(futs):
                fut.add_done_callback(functools.partial(on_done, i))
            await asyncio.gather(*futs, return_exceptions=True)
            flush()
            if _events.REC.enabled:
                self.worker._maybe_flush_spans()
            return {"n": len(wires)}

        async def run_one(i: int, wire: Dict) -> None:
            try:
                reply = await self.handle_push_task(conn, wire)
            except BaseException as e:  # noqa: BLE001 — per-item blast radius
                reply = {"batch_item_error": repr(e)}
            out.append((i, reply))
            if not armed[0]:
                armed[0] = True
                asyncio.get_running_loop().call_soon(flush)

        await asyncio.gather(*[run_one(i, w) for i, w in enumerate(wires)])
        flush()
        return {"n": len(wires)}

    async def handle_push_task_batch(self, conn, wires: List[Dict]
                                     ) -> List[Dict]:
        """One frame, many sequenced pushes (the submitter's
        _ActorState._push_batch): fan the specs through the normal
        per-task paths — creation order keeps the drainer/chain ordering —
        and reply with the results as one list. Handler-level failures are
        mapped to PER-ITEM error replies so one bad spec in a 64-task
        frame keeps the blast radius of a single PushTask (the submitter
        would otherwise fail the whole frame as an actor death)."""
        replies = await asyncio.gather(
            *[self.handle_push_task(conn, w) for w in wires],
            return_exceptions=True)
        return [r if not isinstance(r, BaseException)
                else {"batch_item_error": repr(r)} for r in replies]

    # ---------------------------------------------------- batched execution
    def _run_on_drainer(self, spec: TaskSpec, assigned: Dict) -> "asyncio.Future":
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        with self._exec_mu:
            self._exec_queue.append((spec, assigned, fut, loop))
            start_drainer = not self._drainer_active
            if start_drainer:
                self._drainer_active = True
        if start_drainer:
            # actor instances carry thread-affine state (sqlite handles,
            # threading.local set in __init__): drain on the same pool the
            # constructor ran on
            pool = (self._actor_pool if self._actor_pool is not None
                    else self._task_pool)
            pool.submit(self._drain_exec)
        return fut

    def _drain_exec(self) -> None:
        while True:
            with self._exec_mu:
                if not self._exec_queue:
                    self._drainer_active = False
                    return
                spec, assigned, fut, loop = self._exec_queue.popleft()
            t0 = time.monotonic()
            try:
                reply = self._execute_sync(spec, assigned)
                err = None
                if isinstance(reply, dict):
                    # pure execution time (queue wait excluded) so the
                    # owner's adaptive-pipelining EMA doesn't self-inflate
                    reply["exec_ms"] = (time.monotonic() - t0) * 1000.0
            except BaseException as e:  # noqa: BLE001 — incl. SystemExit
                reply, err = None, e
            self._post_result(loop, fut, reply, err)

    def _post_result(self, loop, fut, reply, err) -> None:
        with self._res_mu:
            self._results.append((fut, reply, err))
            if self._res_armed:
                return
            self._res_armed = True
        try:
            loop.call_soon_threadsafe(self._flush_results)
        except RuntimeError:
            pass  # loop closed during shutdown

    def _flush_results(self) -> None:
        while True:
            with self._res_mu:
                if not self._results:
                    self._res_armed = False
                    return
                batch = list(self._results)
                self._results.clear()
            for fut, reply, err in batch:
                if fut.done():
                    continue
                if err is not None:
                    fut.set_exception(err)
                else:
                    fut.set_result(reply)

    async def _ordered_actor_task(self, conn, spec: TaskSpec) -> Dict:
        key = id(conn)
        prev = self._chain_tail.get(key)
        done = asyncio.get_running_loop().create_future()
        self._chain_tail[key] = done
        if prev is not None:
            await prev
        try:
            return await self._execute_async(spec, {})
        finally:
            done.set_result(None)
            if self._chain_tail.get(key) is done:
                del self._chain_tail[key]

    async def _execute_async(self, spec: TaskSpec, assigned: Dict) -> Dict:
        method = None
        is_async = False
        if spec.task_type == ACTOR_TASK:
            method = getattr(self.worker.actor_instance, spec.actor_method, None)
            is_async = method is not None and inspect.iscoroutinefunction(method)
        if is_async:
            actor_loop = self._ensure_actor_loop()

            async def run_on_actor_loop():
                if self._actor_sem is None:
                    self._actor_sem = asyncio.Semaphore(self._max_concurrency)
                async with self._actor_sem:
                    return await self._run_async_method(spec, method)

            fut = asyncio.run_coroutine_threadsafe(
                run_on_actor_loop(), actor_loop)
            return await asyncio.wrap_future(fut)
        pool = self._actor_pool if spec.task_type == ACTOR_TASK and self._actor_pool \
            else self._task_pool
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(pool, self._execute_sync, spec, assigned)

    # ------------------------------------------------------------ execution
    def _resolve_args(self, spec: TaskSpec):
        args = [self._materialize(entry) for entry in spec.args]
        kwargs = {k: self._materialize(v) for k, v in spec.kwargs.items()}
        return args, kwargs

    def _materialize(self, entry) -> Any:
        kind = entry[0]
        if kind in ("v", "iv"):
            return self.worker.serialization_context.deserialize(memoryview(entry[1]))
        if kind == "r":
            ref = _rebuild_ref(bytes(entry[1]), entry[2])
            return self.worker._get_one(ref, timeout=None)
        if kind == "x":
            # cross-language by-value arg: plain msgpack, no pickle
            # (reference: cross_language.py msgpack arg encoding)
            import msgpack

            return msgpack.unpackb(entry[1], raw=False)
        raise ValueError(f"bad arg entry kind {kind}")

    def _execute_sync(self, spec: TaskSpec, assigned: Dict) -> Dict:
        if os.environ.get("RAY_TPU_DEBUG"):
            from ray_tpu._private import worker as _wm
            print(f"EXEC pid={os.getpid()} fn={spec.function_name} "
                  f"gw_none={_wm.global_worker is None} "
                  f"gw_is_self={_wm.global_worker is self.worker}",
                  file=sys.stderr, flush=True)
        _apply_accelerator_env(assigned)
        ctx = self.worker.current_task_info
        ctx.task_id = TaskID(spec.task_id)
        ctx.task_name = spec.function_name
        ctx.placement_group_id = spec.placement_group_id
        start = time.time()
        # flight recorder (ISSUE 14): the trace context rode the spec wire
        # from the submitter; the OPEN marker written before user code runs
        # is the post-mortem breadcrumb a kill -9 leaves behind
        rec = _events.REC
        tc = spec.trace_ctx if rec.enabled else None
        exec_span = cur_tok = 0
        if tc is not None:
            exec_span = rec.next_id()
            rec.open_marker("exec::" + spec.function_name, "exec",
                            tc[0], exec_span, tc[1],
                            {"task": spec.task_id.hex()[:16]})
            cur_tok = _events.set_current((tc[0], exec_span))
        try:
            if spec.runtime_env:
                from ray_tpu.runtime_env import setup_runtime_env

                setup_runtime_env(spec.runtime_env,
                                  os.environ.get("RAY_TPU_SESSION_DIR"))
            if tc is not None:
                t_args = time.time()
                args, kwargs = self._resolve_args(spec)
                rec.record("arg_resolve", "exec", t_args,
                           time.time() - t_args, tc[0], rec.next_id(),
                           exec_span)
            else:
                args, kwargs = self._resolve_args(spec)
            if spec.task_type == ACTOR_TASK:
                if spec.actor_method == "__ray_apply__":
                    # reserved dispatch: args[0] is a callable run WITH the
                    # actor instance (compiled-DAG stage loops ride this —
                    # reference compiled_dag_node.py attaches its executor
                    # loop to participating actors the same way)
                    result = args[0](self.worker.actor_instance, *args[1:],
                                     **kwargs)
                else:
                    fn = getattr(self.worker.actor_instance, spec.actor_method)
                    result = fn(*args, **kwargs)
            else:
                fn = load_function(spec.function_id, spec.function_blob,
                                   self.worker, name=spec.function_name)
                if spec.replay_seed is not None:
                    # lineage replay determinism (ISSUE 17): the seed was
                    # stamped at FIRST submission, so the original run and
                    # every replay draw identical randomness
                    _seed_task_rng(spec.replay_seed)
                result = fn(*args, **kwargs)
            if inspect.iscoroutine(result):
                # async callable that evaded static detection (e.g. attached
                # via __getattr__): run it to completion on this thread
                result = asyncio.run(result)
            # exec duration for the store's lineage-aware eviction cost
            # model (cheap-to-replay copies are preferred victims)
            ctx.exec_ms = (time.time() - start) * 1000.0
            if tc is not None:
                t_ret = time.time()
                reply = self._package_returns(spec, result)
                rec.record("return_put", "exec", t_ret,
                           time.time() - t_ret, tc[0], rec.next_id(),
                           exec_span)
                return reply
            return self._package_returns(spec, result)
        except SystemExit:
            raise
        except BaseException as e:  # noqa: BLE001 — user errors cross the wire
            err = RayTaskError.from_exception(e, spec.function_name)
            data = self.worker._serialize_value(err).to_bytes()
            return {
                "error": True,
                "error_message": f"{type(e).__name__}: {e}",  # xlang-readable
                "error_inline": data,  # streaming tasks have no return slots
                "returns": [
                    {"inline": data, "is_exception": True}
                    for _ in range(spec.num_returns)
                ],
            }
        finally:
            if tc is not None:
                rec.record("exec::" + spec.function_name, "exec", start,
                           time.time() - start, tc[0], exec_span, tc[1],
                           {"task": spec.task_id.hex()[:16]})
                _events.reset_current(cur_tok)
            ctx.task_id = None
            ctx.task_name = None
            ctx.placement_group_id = None

    def _ensure_actor_loop(self) -> asyncio.AbstractEventLoop:
        if self._actor_loop is None:
            import threading

            loop = asyncio.new_event_loop()
            ready = threading.Event()

            def run():
                asyncio.set_event_loop(loop)
                loop.call_soon(ready.set)
                loop.run_forever()

            t = threading.Thread(target=run, daemon=True,
                                 name="async-actor-loop")
            t.start()
            ready.wait()
            self._actor_loop = loop
        return self._actor_loop

    async def _run_async_method(self, spec: TaskSpec, method) -> Dict:
        loop = asyncio.get_running_loop()
        rec = _events.REC
        tc = spec.trace_ctx if rec.enabled else None
        exec_span = 0
        cur_tok = None
        t0 = time.time()
        if tc is not None:
            exec_span = rec.next_id()
            rec.open_marker("exec::" + spec.function_name, "exec",
                            tc[0], exec_span, tc[1],
                            {"task": spec.task_id.hex()[:16], "async": 1})
            # awaited user code inherits this coroutine's context, so a
            # ray_tpu.get() inside the async method nests under exec::
            cur_tok = _events.set_current((tc[0], exec_span))
        try:
            args, kwargs = await loop.run_in_executor(
                None, lambda: self._resolve_args(spec)
            )
            result = await method(*args, **kwargs)
            return await loop.run_in_executor(
                None, lambda: self._package_returns(spec, result)
            )
        except BaseException as e:  # noqa: BLE001
            err = RayTaskError.from_exception(e, spec.function_name)
            data = self.worker._serialize_value(err).to_bytes()
            return {
                "error": True,
                "error_inline": data,
                "returns": [
                    {"inline": data, "is_exception": True}
                    for _ in range(spec.num_returns)
                ],
            }
        finally:
            if tc is not None:
                rec.record("exec::" + spec.function_name, "exec", t0,
                           time.time() - t0, tc[0], exec_span, tc[1],
                           {"task": spec.task_id.hex()[:16], "async": 1})
                _events.reset_current(cur_tok)

    def _lineage_hints(self, spec: TaskSpec) -> Dict:
        """ObjectSealed extras for the store's lineage-aware eviction
        (ISSUE 17): is this copy rebuildable by task replay, and how
        expensive was the producing execution."""
        return {
            "replayable": spec.task_type == NORMAL_TASK
            and spec.max_retries > 0,
            "exec_ms": float(getattr(self.worker.current_task_info,
                                     "exec_ms", 0.0) or 0.0),
        }

    def _package_one(self, spec: TaskSpec, i: int, value: Any,
                     is_exception: bool = False) -> Dict:
        sobj = self.worker._serialize_value(value)
        size = sobj.total_size()
        if size <= CONFIG.inline_object_max_size_bytes:
            return {"inline": sobj.to_bytes(), "is_exception": is_exception}
        oid = ObjectID(spec.task_id + _u32(i))
        from ray_tpu._private import serialization as _ser

        if self.worker.store.contains(oid):
            # Lineage re-execution (recover_task_returns) keeps the
            # original object ids; if this node already holds a sealed
            # copy (it pulled one before the producer died), the native
            # arena refuses a duplicate create — re-announce the
            # existing bytes instead. Deterministic tasks make the copy
            # byte-identical by contract.
            view = self.worker.store.get_view(oid)
            if view is not None:
                used = len(view)
                self.worker._post(self.worker.agent.push_nowait,
                                  "ObjectSealed",
                                  {"object_id": oid.hex(), "size": used,
                                   "zero_copy": _ser.is_zero_copy(view),
                                   "owner": spec.owner_addr,
                                   "task": spec.task_id.hex(),
                                   **self._lineage_hints(spec)})
                return {"plasma": True, "size": used,
                        "node_addr": self.worker.agent_tcp_addr}
        view, handle = self.worker.store.create(oid, size)
        used = sobj.write_into(view)
        self.worker.store.seal(oid, handle)
        # Fire-and-forget (ordering rides the agent socket); the reply to the
        # owner races the seal notification only through the agent, and reads
        # hit tmpfs directly, so the blocking round trip is unnecessary.
        self.worker._post(self.worker.agent.push_nowait,
                          "ObjectSealed",
                          {"object_id": oid.hex(), "size": used,
                           "zero_copy": isinstance(sobj, _ser.ZeroCopyArray),
                           # owner addr + creating task: the agent's object
                           # ledger (ISSUE 15) attributes every sealed byte
                           # and the leak watchdog knows whom to interrogate
                           "owner": spec.owner_addr,
                           "task": spec.task_id.hex(),
                           **self._lineage_hints(spec)})
        return {"plasma": True, "size": used,
                "node_addr": self.worker.agent_tcp_addr}

    def _package_returns(self, spec: TaskSpec, result: Any) -> Dict:
        from ray_tpu._private.function_table import XLANG_PYREF_FID

        if spec.function_id == XLANG_PYREF_FID:
            # cross-language caller: returns must be readable without
            # pickle — plain msgpack, one entry per return slot
            import msgpack

            if spec.num_returns == -1:
                raise ValueError(
                    "cross-language tasks do not support streaming "
                    "returns (num_returns=-1)")
            if spec.num_returns == 0:
                return {"returns": []}
            values = [result] if spec.num_returns == 1 else list(result)
            if len(values) != spec.num_returns:
                raise ValueError(
                    f"task declared num_returns={spec.num_returns} but "
                    f"returned {len(values)} values")
            try:
                return {"returns": [
                    {"xlang": msgpack.packb(v, use_bin_type=True)}
                    for v in values]}
            except (TypeError, ValueError) as e:
                raise TypeError(
                    f"cross-language task {spec.function_name!r} returned "
                    f"a value msgpack cannot encode: {e}") from e
        if spec.num_returns == -1:
            return self._package_streaming(spec, result)
        if spec.num_returns == 0:
            return {"returns": []}
        if spec.num_returns == 1:
            values = [result]
        else:
            values = list(result)
            if len(values) != spec.num_returns:
                raise ValueError(
                    f"task declared num_returns={spec.num_returns} but returned "
                    f"{len(values)} values"
                )
        return {"returns": [self._package_one(spec, i, v)
                            for i, v in enumerate(values)]}

    def _package_streaming(self, spec: TaskSpec, result: Any) -> Dict:
        """Consume a generator, reporting each yield to the owner as it is
        produced (reference: core_worker streaming generator path,
        ReportGeneratorItemReturns). The per-item ack round-trip is the
        backpressure: a wedged owner stalls the producer, not memory."""
        owner = spec.owner_addr

        def report(i: int, ret: Dict) -> None:
            async def call():
                client = await self.worker._owner_client(owner)
                # raylint: disable=R6 -- long-poll by design: the per-item
                # ack IS the backpressure (a slow owner stalls the producer
                # indefinitely and legitimately); owner death fails this
                # call fast via the PR 5 node-channel fail-fast path
                return await client.call(
                    "StreamingReturn",
                    {"task_id": spec.task_id.hex(), "index": i, "ret": ret})

            self.worker._acall(call())

        count = 0
        failed = False
        try:
            for value in result:
                report(count, self._package_one(spec, count, value))
                count += 1
        except BaseException as e:  # noqa: BLE001 — becomes the next item
            err = RayTaskError.from_exception(e, spec.function_name)
            report(count, self._package_one(spec, count, err,
                                            is_exception=True))
            count += 1
            failed = True
        # streaming_failed: the stream still finishes cleanly (the exception
        # is delivered as the last ref) but task-event observability must
        # record FAILED, not FINISHED
        return {"returns": [], "streaming_count": count,
                "streaming_failed": failed}

    # --------------------------------------------------------------- actors
    async def become_actor(self, payload: Dict) -> None:
        spec = payload["spec"]
        self._actor_id = ActorID.from_hex(payload["actor_id"])
        self._max_concurrency = spec.get("max_concurrency", 1)
        self._actor_pool = ThreadPoolExecutor(
            max_workers=max(1, self._max_concurrency),
            thread_name_prefix="actor-exec",
        )
        _apply_accelerator_env(payload.get("assigned_instances") or {})
        loop = asyncio.get_running_loop()

        def construct():
            if spec.get("runtime_env"):
                from ray_tpu.runtime_env import setup_runtime_env

                setup_runtime_env(spec["runtime_env"],
                                  os.environ.get("RAY_TPU_SESSION_DIR"))
            cls = ser.loads(spec["class_blob"])
            args = [self._materialize(e) for e in spec.get("init_args", [])]
            kwargs = {k: self._materialize(v)
                      for k, v in spec.get("init_kwargs", {}).items()}
            self.worker.job_id = JobID.from_hex(spec["job_id"]) if spec.get("job_id") \
                else self.worker.job_id
            self.worker.actor_instance = cls(*args, **kwargs)

        try:
            await loop.run_in_executor(self._actor_pool, construct)
            inst = self.worker.actor_instance
            self._actor_has_async = any(
                inspect.iscoroutinefunction(m)
                for _, m in inspect.getmembers(
                    type(inst), predicate=callable)
            ) or any(
                inspect.iscoroutinefunction(v)
                for v in list(vars(inst).values())
                if callable(v))
        except BaseException as e:  # noqa: BLE001
            traceback.print_exc()
            try:
                # outage-queued (head_call machinery): under lazy worker
                # head connect the link may still be coming up — the
                # precise failure reason should survive that window
                await self.worker._head_call_async(
                    "ActorDied",
                    {"actor_id": payload["actor_id"],
                     "reason": f"creation task failed: {e!r}"},
                    timeout=CONFIG.control_rpc_timeout_s,
                )
            finally:
                os._exit(1)
            return
        # which of the agent's `actor_start::{warm_hit, demand_hit, fork}`
        # this worker was: the ring's record of its boot waited for it
        book = _events.startup_stats()
        book["actor_start"] = payload.get("actor_start", "")
        if "startup.boot" in book:
            _events.startup_record(
                "startup.boot", book["at"]["startup.boot"],
                book["startup.boot"], {"actor_start": book["actor_start"]})
        self.worker.current_actor_id = self._actor_id
        pg = spec.get("pg")
        if pg:
            self.worker.current_placement_group_id = pg[0]
        # The readiness report MUST land or this process must die: a
        # dropped report (seen under 1,000-actor bursts) would otherwise
        # leave a zombie — alive, never ALIVE in the head, its callers
        # hanging forever. It rides the AGENT relay (unix socket →
        # coalesced ActorReadyBatch, ISSUE 10): the agent acks only after
        # the head acked, so the at-least-once contract is end-to-end and
        # a creation burst costs one head RPC per flush window instead of
        # one per worker. Persistent failure exits so the agent reports
        # ActorDied and callers fail fast.
        ready_payload = {
            "actor_id": payload["actor_id"],
            "addr": self.worker.direct_addr(),
            "node_id": self.worker.node_id,
            "pid": os.getpid(),
        }
        for attempt in range(10):
            try:
                await self.worker.agent.call(
                    "ReportActorReady", ready_payload,
                    timeout=CONFIG.control_rpc_timeout_s)
                break
            except Exception:
                if attempt == 9:
                    traceback.print_exc()
                    os._exit(1)
                await asyncio.sleep(0.5 + 0.5 * attempt)


def _u32(i: int) -> bytes:
    import struct

    return struct.pack("<I", i)


def _apply_accelerator_env(assigned: Dict[str, List[int]]) -> None:
    if assigned.get("TPU"):
        from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager
        from ray_tpu._private.compile_cache import place_compile_cache

        with _events.startup_span("chip_bind",
                                  {"chips": len(assigned["TPU"])}):
            TPUAcceleratorManager.set_visible_accelerator_ids(
                assigned["TPU"])
            place_compile_cache()
        # a process that holds a chip: its first `import jax`, whoever
        # makes it, is a phase of its start-up
        _events.time_first_import("jax", "import_jax")
    if "GPU" in assigned:
        os.environ["CUDA_VISIBLE_DEVICES"] = ",".join(
            str(i) for i in assigned["GPU"]
        )


# ----------------------------------------------------------- profiling
def _sample_stacks_sync(duration_s: float, interval_s: float) -> Dict:
    """py-spy-style in-process stack sampler (reference:
    dashboard/modules/reporter/profile_manager.py:61-97 launches py-spy;
    this image has none, so the worker samples sys._current_frames itself).
    Returns {folded_stack: count} — flamegraph.pl / speedscope input."""
    import collections

    counts: "collections.Counter" = collections.Counter()
    deadline = time.monotonic() + duration_s
    me = threading.get_ident()
    while time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            # walk f_back directly: traceback.extract_stack would stat()
            # and read source files via linecache on every sample, skewing
            # the profile being measured
            parts = []
            f = frame
            while f is not None:
                code = f.f_code
                parts.append(f"{code.co_name} "
                             f"({os.path.basename(code.co_filename)}:"
                             f"{f.f_lineno})")
                f = f.f_back
            if parts:
                counts[";".join(reversed(parts))] += 1
        time.sleep(interval_s)
    return dict(counts)


async def _handle_sample_stacks(conn, p) -> Dict:
    duration = min(float((p or {}).get("duration_s", 2.0)), 60.0)
    interval = max(float((p or {}).get("interval_s", 0.01)), 0.001)
    folded = await asyncio.get_running_loop().run_in_executor(
        None, _sample_stacks_sync, duration, interval)
    return {"pid": os.getpid(), "duration_s": duration, "folded": folded}


async def _handle_capture_jax_trace(conn, p) -> Dict:
    """Capture an XLA device trace with jax.profiler (SURVEY §5: hook
    jax.profiler into the reporter surface; loadable in TensorBoard/
    Perfetto). Blocks for duration_s while the worker keeps executing."""
    p = p or {}
    duration = min(float(p.get("duration_s", 2.0)), 120.0)
    out_dir = p.get("out_dir") or os.path.join(
        os.environ.get("RAY_TPU_SESSION_DIR", "/tmp"), "jax_traces",
        f"worker-{os.getpid()}-{int(time.time())}")
    os.makedirs(out_dir, exist_ok=True)

    def capture():
        import jax

        jax.profiler.start_trace(out_dir)
        time.sleep(duration)
        jax.profiler.stop_trace()
        files = []
        for root, _dirs, names in os.walk(out_dir):
            files += [os.path.relpath(os.path.join(root, n), out_dir)
                      for n in names]
        return files

    try:
        files = await asyncio.get_running_loop().run_in_executor(
            None, capture)
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}", "trace_dir": out_dir}
    return {"pid": os.getpid(), "trace_dir": out_dir, "files": files}


def _boot() -> Worker:
    """From the process's entry until it is ready for its first task."""
    agent_sock = os.environ["RAY_TPU_AGENT_SOCK"]
    from ray_tpu._private import lifecycle
    from ray_tpu._private import sanitizer as _sanitizer
    from ray_tpu._private.ids import WorkerID

    # before Worker() so every runtime lock is created through the
    # wrapping factories (RAY_TPU_SANITIZE=1 debug runs; no-op default)
    _sanitizer.maybe_install()

    # fate-share with the node agent (RAY_TPU_PARENT_PID): the park loop
    # below exits when the agent CONNECTION drops, but a worker stuck in
    # user code / a jitted computation never reaches that check — the
    # PDEATHSIG + supervisor-poll watchdog covers it (escalates to
    # os._exit if SIGTERM is swallowed). Workers poll SLOWLY: PDEATHSIG
    # chains cover the common death paths, and a 1s poll across 1,000
    # workers is thousands of liveness syscalls/s (ISSUE 10); the
    # registry sweep bounds the rare orphan window regardless.
    lifecycle.fate_share_with_parent(poll_s=5.0)

    worker = Worker()
    worker.worker_id = WorkerID.from_hex(os.environ["RAY_TPU_WORKER_ID"])
    executor = Executor(worker)

    # Executor routes must exist before registration makes us leasable.
    worker.direct_server.add_handler("PushTask", executor.handle_push_task)
    worker.direct_server.add_handler("PushTaskBatchStream",
                                     executor.handle_push_task_batch_stream)
    worker.direct_server.add_handler("PushTaskBatch",
                                     executor.handle_push_task_batch)
    worker.direct_server.add_handler("SampleStacks", _handle_sample_stacks)
    worker.direct_server.add_handler("CaptureJaxTrace",
                                     _handle_capture_jax_trace)

    base_push = worker._on_agent_push

    async def on_agent_push(method: str, payload):
        if method == "BecomeActor":
            await worker.ready_event.wait()
            await executor.become_actor(payload)
        else:
            # keep the base dispatch: executor workers submitting nested
            # work use the same lease plane as drivers
            await base_push(method, payload)

    worker._on_agent_push = on_agent_push  # type: ignore[method-assign]
    worker.connect(agent_sock, mode=Worker.MODE_WORKER)
    return worker


def main() -> None:
    # the ring is armed in `connect`: its record of this span waits for
    # `become_actor`, which knows how the agent came by this worker
    with _events.startup_span("boot"):
        worker = _boot()
    if os.environ.get("RAY_TPU_BOOT_TRACE"):
        # time-to-leasable per worker (stderr -> worker .err log): the
        # number the warm pool exists to amortize
        print(f"BOOT_TRACE pid={os.getpid()} "
              f"ready_ms={_events.STARTUP['startup.boot'] * 1000:.1f} "
              f"phases={getattr(worker, '_boot_trace', {})}",
              file=sys.stderr, flush=True)

    # Park the main thread; all work happens on the IO loop + executors.
    try:
        while worker.connected and worker.agent.connected:
            time.sleep(CONFIG.worker_park_poll_s)
    except KeyboardInterrupt:
        pass
    # fatal-exit breadcrumb (agent gone / interrupted): the mmap ring is
    # already durable, the jsonl dump just makes it human-greppable
    _events.REC.dump_local("worker_exit")
    os._exit(0)


if __name__ == "__main__":
    main()
