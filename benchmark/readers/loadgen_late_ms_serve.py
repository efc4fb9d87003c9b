"""Reader of `loadgen_late_ms.serve`; what it reads is in metrics/loadgen_late_ms.serve.json."""

from benchmark.harness import stats


def read(view, metric):
    late = view["obs"]["late_s"]
    return 1e3 * stats.percentile(late, 95) if late else None
