"""Reader of `fit_overhead_s.train`; what it reads is in metrics/fit_overhead_s.train.json."""


def read(view, metric):
    return view["obs"]["fit_wall_s"] - view["obs"]["loop_wall_s"]
