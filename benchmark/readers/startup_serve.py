"""Readers of the six metrics of a serving replica's start-up:
`startup_runtime_s.serve`, `startup_backend_s.serve`,
`startup_weights_s.serve`, `startup_trace_lower_s.serve`,
`startup_backend_compile_s.serve` and `startup_cache_hit_pct.serve`; what
each reads is in its file under metrics/.

All read `engine_stats()` as the window's end found it
(`obs["engine_stats_end"]`): its `startup`, the replica process's book of
its own start-up's phases (`ray_tpu/_private/events.py::startup_stats`),
and its `compiles`, what jax said of every compilation in that process and
what the persistent cache did with it
(`ray_tpu/_private/compile_cache.py::compile_stats`); the first also reads
`obs["replica_ready_s"]`. Nothing of the trace. Both books came with PR 57.
A program whose `engine_stats()` has NEITHER predates them (the parent
commit, which the driver runs under these files too): every reader says so
on standard error and gives `NOT_MEASURED`, a number no reading can be,
because `harness/lastline.py` refuses a traced line that leaves a listed
metric out, as `readers/engine_counters_serve.py` does and for its reason.
A program that has one book and lacks the other, or lacks a name inside
one, has broken what it owes the benchmark: an error that names it.
"""

import sys

NOT_MEASURED = -1.0
BOOKS = ("startup", "compiles")


def _book(view, metric, book, *names):
    """``engine_stats_end[book]``'s ``names``, in that order; None for a
    program that predates both books."""
    stats = view["obs"]["engine_stats_end"]
    if not any(b in stats for b in BOOKS):
        print(f"[bench] {metric['name']}: the program's engine_stats() has "
              f"none of {list(BOOKS)}: it predates them, and the metric "
              f"reads {NOT_MEASURED} (not measured)",
              file=sys.stderr, flush=True)
        return None
    if book not in stats:
        raise KeyError(f"{metric['name']}: the program's engine_stats() "
                       f"lacks {book!r}")
    missing = [n for n in names if n not in stats[book]]
    if missing:
        raise KeyError(f"{metric['name']}: the program's engine_stats()"
                       f"[{book!r}] lacks {missing}")
    return [stats[book][n] for n in names]


def runtime_s(view, metric):
    got = _book(view, metric, "startup", "startup.construct")
    if got is None:
        return NOT_MEASURED
    return view["obs"]["replica_ready_s"] - got[0]


def backend_s(view, metric):
    got = _book(view, metric, "startup", "startup.import_jax",
                "startup.devices")
    return NOT_MEASURED if got is None else got[0] + got[1]


def weights_s(view, metric):
    got = _book(view, metric, "startup", "weights_ready_s")
    return NOT_MEASURED if got is None else got[0]


def trace_lower_s(view, metric):
    got = _book(view, metric, "compiles", "trace_s", "lower_s")
    return NOT_MEASURED if got is None else got[0] + got[1]


def backend_compile_s(view, metric):
    got = _book(view, metric, "compiles", "backend_s", "programs", "slowest")
    if got is None:
        return NOT_MEASURED
    seconds, programs, slowest = got
    named = ", ".join(f"{name} {python_s:.2f}+{xla_s:.2f}"
                      for name, python_s, xla_s in slowest[:5])
    print(f"[bench] {metric['name']}: {programs} programs, the slowest "
          f"(trace and lowering + back end, s): {named}",
          file=sys.stderr, flush=True)
    return seconds


def cache_hit_pct(view, metric):
    got = _book(view, metric, "compiles", "cache_hits", "cache_requests")
    if got is None:
        return NOT_MEASURED
    hits, requests = got
    return 100.0 * hits / requests if requests else 0.0
