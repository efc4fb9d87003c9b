"""Reader of `ttft_p50_ms.serve`; what it reads is in metrics/ttft_p50_ms.serve.json."""

from benchmark.harness import stats


def read(view, metric):
    ttft = view["obs"]["ttft_s"]
    return 1e3 * stats.percentile(ttft, 50) if ttft else None
