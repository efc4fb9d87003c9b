"""Reader of `collective_ms.train`; what it reads is in metrics/collective_ms.train.json."""

from benchmark.readers import common


def read(view, metric):
    return common.ops_ms_per_step(view["trace"], metric["match"])
