"""Reader of `expert_load_imbalance.serve`; what it reads is in metrics/expert_load_imbalance.serve.json."""


def read(view, metric):
    stats = view["obs"]["engine_stats_end"]
    mean = stats.get("expert_pairs_mean")
    return stats["expert_pairs_fullest"] / mean if mean else None
