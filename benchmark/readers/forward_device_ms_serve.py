"""Reader of `forward_device_ms.serve`; what it reads is in metrics/forward_device_ms.serve.json."""

from benchmark.readers import common


def read(view, metric):
    return common.busy_ms_per_step(view["trace"])
