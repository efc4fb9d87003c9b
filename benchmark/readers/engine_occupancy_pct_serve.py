"""Reader of `engine_occupancy_pct.serve`; what it reads is in metrics/engine_occupancy_pct.serve.json."""


def read(view, metric):
    obs = view["obs"]
    width = view["cell"]["engine"]["max_batch_size"]
    return 100.0 * obs["engine_emitted"] / (obs["engine_steps"] * width) if obs["engine_steps"] else None
