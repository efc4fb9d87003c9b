"""Readers of `engine_host_ms.serve`, `engine_active_pct.serve`,
`engine_queue_wait_ms.serve` and `engine_queue_wait_max_ms.serve`; what each
reads is in its file under metrics/.

All four read `engine_stats()` as the window's end found it
(`obs["engine_stats_end"]`) and `obs["window_s"]`, and nothing of the trace:
whole-window numbers from inside the program. The counters came with PR 38.
A program that has NONE of them predates them (the parent commit, which the
driver runs under these files too): every reader says so on standard error
and gives `NOT_MEASURED`, a number no reading can be, because
`harness/lastline.py` refuses a traced line that leaves a listed metric out.
A program that has some and lacks another has broken what it owes the
benchmark: an error that names the counter.
"""

import sys

NOT_MEASURED = -1.0
# what `ContinuousBatchingEngine.stats()` and `LlamaGenerator.STEP_COUNTERS`
# gained in PR 38
SINCE_PR_38 = ("active_s", "joined", "queue_wait_s", "queue_wait_max_s",
               "step_device_s")


def _counters(view, metric, *names):
    """``engine_stats_end``'s ``names``, in that order; None for a program
    that predates every counter of ``SINCE_PR_38``."""
    stats = view["obs"]["engine_stats_end"]
    if not any(c in stats for c in SINCE_PR_38):
        print(f"[bench] {metric['name']}: the program's engine_stats() has "
              f"none of {list(SINCE_PR_38)}: it predates them, and the "
              f"metric reads {NOT_MEASURED} (not measured)",
              file=sys.stderr, flush=True)
        return None
    missing = [n for n in names if n not in stats]
    if missing:
        raise KeyError(f"{metric['name']}: the program's engine_stats() "
                       f"lacks {missing}")
    return [stats[n] for n in names]


def host_ms(view, metric):
    got = _counters(view, metric, "active_s", "step_device_s", "steps")
    if got is None:
        return NOT_MEASURED
    active_s, device_s, steps = got
    return 1e3 * (active_s - device_s) / steps if steps else 0.0


def active_pct(view, metric):
    got = _counters(view, metric, "active_s")
    if got is None:
        return NOT_MEASURED
    return 100.0 * got[0] / view["obs"]["window_s"]


def queue_wait_ms(view, metric):
    got = _counters(view, metric, "queue_wait_s", "joined")
    if got is None:
        return NOT_MEASURED
    wait_s, joined = got
    return 1e3 * wait_s / joined if joined else 0.0


def queue_wait_max_ms(view, metric):
    got = _counters(view, metric, "queue_wait_max_s")
    return NOT_MEASURED if got is None else 1e3 * got[0]
