"""Reader of `replica_ready_s.serve`; what it reads is in metrics/replica_ready_s.serve.json."""


def read(view, metric):
    return view["obs"]["replica_ready_s"]
