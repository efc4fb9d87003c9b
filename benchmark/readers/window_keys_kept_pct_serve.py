"""Reader of `mellum2_window_keys_kept_pct.serve`; what it reads is in metrics/mellum2_window_keys_kept_pct.serve.json."""


def read(view, metric):
    """None where the program counts no `window_keys_seen` (a checkout
    from before the counter) or no window layer's query was live."""
    stats = view["obs"]["engine_stats_end"]
    seen = stats.get("window_keys_seen")
    return 100.0 * stats.get("window_keys_kept", 0) / seen if seen else None
