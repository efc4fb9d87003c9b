"""Reader of `engine_step_ms.serve`; what it reads is in metrics/engine_step_ms.serve.json."""


def read(view, metric):
    obs = view["obs"]
    return 1e3 * obs["window_s"] / obs["engine_steps"] if obs["engine_steps"] else None
