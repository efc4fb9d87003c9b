"""What several readers share: operations by name, the idle share."""

import re


def ops_ms_per_step(trace, pattern):
    """Self milliseconds a step of the traced operations whose name
    matches ``pattern``; None when nothing matches or no step was traced."""
    rx = re.compile(pattern)
    total = sum(secs for name, secs, _ in trace["ops"] if rx.search(name))
    if not total or not trace["steps"]:
        return None
    return 1e3 * total / trace["steps"]


def idle_pct(device):
    return 100.0 * (1.0 - device["busy_s"] / device["window_s"])


def busy_ms_per_step(trace):
    """Device busy milliseconds a step over the traced window."""
    return 1e3 * trace["busy_s"] / trace["steps"] if trace["steps"] else None
