"""Reader of `dots3_index_keys_kept_pct.serve`; what it reads is in metrics/dots3_index_keys_kept_pct.serve.json."""


def read(view, metric):
    stats = view["obs"]["engine_stats_end"]
    seen = stats.get("index_keys_seen")
    return 100.0 * stats.get("index_keys_kept", 0) / seen if seen else None
