"""Reader of `device_idle_pct.train`; what it reads is in metrics/device_idle_pct.train.json."""

from benchmark.readers import common


def read(view, metric):
    return common.idle_pct(view["device"])
