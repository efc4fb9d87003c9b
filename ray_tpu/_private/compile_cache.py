"""Where the persistent XLA compile cache goes, and what it did.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
here names another directory. Where it is not, processes that compile for
the chip (train workers, serve replicas) share ``<checkout>/.jax_cache``: a
fixed path, because the path is part of how a later process finds what an
earlier one compiled — no temp name, pid or time in it.

``watch_compiles()`` registers the process's one listener of jax's own
compile events; ``compile_stats()`` is the book it keeps. The listener runs
where jax compiles and nowhere else: a cached dispatch raises no event.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict

from ray_tpu._private import events

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

# jax 0.9's events, by name. The three durations carry `fun_name`; a jitted
# function traced inside another's trace raises its own trace event inside
# the outer one's, so only a thread's outermost counts (`_InFlight.depth`).
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_COUNTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses"}
CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s"}
SLOWEST_KEPT = 16


def place_compile_cache() -> str:
    """Return the cache directory in force, setting the default when the
    environment names none. Safe before or after ``import jax``; after it,
    the process's compilations are counted from here on."""
    cache_dir = os.environ.get(ENV_CACHE_DIR)
    if not cache_dir:
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        cache_dir = os.path.join(checkout, ".jax_cache")
        os.environ[ENV_CACHE_DIR] = cache_dir
        jax = sys.modules.get("jax")
        if jax is not None:  # jax read the (unset) variable at its import
            jax.config.update("jax_compilation_cache_dir", cache_dir)
    watch_compiles()
    return cache_dir


class _InFlight(threading.local):
    """A thread's compilation in flight: how deep in traces it is, and
    Python's seconds (tracing, lowering) of the program so far."""

    depth = 0
    python_s = 0.0


class _Watch:
    """The listeners' state: the snapshot, and each thread's compilation
    in flight."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.registered = False
        self.local = _InFlight()
        self.snapshot: Dict[str, Any] = {
            "programs": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache_requests": 0, "cache_hits": 0, "cache_misses": 0,
            "cache_retrieval_s": 0.0, "cache_saved_s": 0.0, "slowest": []}

    def add(self, **moved) -> None:
        """Replace the snapshot by one with ``moved`` added: a reader holds
        a whole book, and holding it costs nothing."""
        with self.lock:
            new = dict(self.snapshot)
            slow = moved.pop("slowest", None)
            for key, by in moved.items():
                new[key] += by
            if slow is not None:
                new["slowest"] = sorted(
                    new["slowest"] + [slow], key=lambda p: -(p[1] + p[2])
                )[:SLOWEST_KEPT]
            self.snapshot = new

    def on_start(self, event: str, value, **kw) -> None:
        if event == TRACE_EVENT:
            self.local.depth += 1

    def on_seconds(self, event: str, secs: float, **kw) -> None:
        local = self.local
        if event == TRACE_EVENT:
            local.depth = max(0, local.depth - 1)
            if local.depth == 0:
                local.python_s += secs
                self.add(trace_s=secs)
        elif event == LOWER_EVENT:
            local.python_s += secs
            self.add(lower_s=secs)
        elif event == BACKEND_EVENT:
            python_s, local.python_s = local.python_s, 0.0
            name = str(kw.get("fun_name", ""))
            self.add(programs=1, backend_s=secs,
                     slowest=(name, python_s, secs))
            rec = events.REC
            if rec.enabled:
                # a child of whatever span encloses the compilation on its
                # thread: in a step, `llm.device`
                ctx = events.current_ctx()
                if ctx is not None:
                    rec.record("compile", "compile", time.time() - secs,
                               secs, ctx[0], rec.next_id(), ctx[1],
                               {"fun_name": name,
                                "trace_lower_s": round(python_s, 6)})
        elif event in CACHE_SECONDS:
            self.add(**{CACHE_SECONDS[event]: secs})

    def on_event(self, event: str, **kw) -> None:
        if event in CACHE_COUNTS:
            self.add(**{CACHE_COUNTS[event]: 1})


_WATCH = _Watch()


def watch_compiles() -> bool:
    """Register the process's one listener of jax's compile events, the
    first time jax is there to register with; False while it is not
    imported. Asked again, it registers nothing more."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    with _WATCH.lock:
        if not _WATCH.registered:
            _WATCH.registered = True
            monitoring = jax.monitoring
            monitoring.register_scalar_listener(_WATCH.on_start)
            monitoring.register_event_duration_secs_listener(
                _WATCH.on_seconds)
            monitoring.register_event_listener(_WATCH.on_event)
    return True


def compile_stats() -> Dict[str, Any]:
    """The compile book as the last event left it: ``programs`` (back-end
    compilations, from the cache or not), ``trace_s`` and ``lower_s``
    (Python's side of them: tracing to a jaxpr, lowering to MLIR, the
    Mosaic kernels' with it; paid whatever is cached), ``backend_s`` (XLA's
    compilation or the cache's retrieval), ``cache_requests``,
    ``cache_hits``, ``cache_misses`` (a miss is an entry WRITTEN: a program
    under the cache's floors is a request and neither), ``cache_retrieval_s``
    and ``cache_saved_s`` as jax reckons them, and ``slowest``, the
    ``SLOWEST_KEPT`` longest programs as ``(fun_name, trace_s + lower_s,
    backend_s)``. A dict nobody mutates: an event replaces it."""
    return _WATCH.snapshot
