"""Reader of `granite_ssm_chunks_live_pct.serve`; what it reads is in metrics/granite_ssm_chunks_live_pct.serve.json."""


def read(view, metric):
    stats = view["obs"]["engine_stats_end"]
    run = stats.get("ssm_chunks_run")
    return 100.0 * stats.get("ssm_chunks_live", 0) / run if run else None
