"""Reader of `expert_matmul_sort_ms.serve`; what it reads is in metrics/expert_matmul_sort_ms.serve.json."""

from benchmark.readers import common


def read(view, metric):
    return common.ops_ms_per_step(view["trace"], metric["match"])
