"""Reader of `ling3_kda_chunks_live_pct.serve`; what it reads is in metrics/ling3_kda_chunks_live_pct.serve.json."""


def read(view, metric):
    stats = view["obs"]["engine_stats_end"]
    run = stats.get("kda_chunks_run")
    return 100.0 * stats.get("kda_chunks_live", 0) / run if run else None
