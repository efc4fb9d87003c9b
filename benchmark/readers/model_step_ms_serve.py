"""Reader of `model_step_ms.serve`; what it reads is in metrics/model_step_ms.serve.json."""

from benchmark.harness import stats


def read(view, metric):
    steps = view["obs"]["model_step_s"]
    return 1e3 * stats.median(steps) if steps else None
