"""Reader of `dsv2_routed_pairs_here_pct.serve`; what it reads is in metrics/dsv2_routed_pairs_here_pct.serve.json."""


def read(view, metric):
    stats = view["obs"]["engine_stats_end"]
    everywhere = stats.get("expert_pairs_all")
    return 100.0 * stats.get("expert_pairs_here", 0.0) / everywhere if everywhere else None
