"""Reader of `input_wait_ms.train`; what it reads is in metrics/input_wait_ms.train.json."""

from benchmark.harness import stats


def read(view, metric):
    waits = view["obs"]["input_wait_s"]
    return 1e3 * stats.median(waits) if waits else None
