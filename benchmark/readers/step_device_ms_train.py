"""Reader of `step_device_ms.train`; what it reads is in metrics/step_device_ms.train.json."""

from benchmark.readers import common


def read(view, metric):
    return common.busy_ms_per_step(view["trace"])
