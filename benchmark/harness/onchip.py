"""What the worker and the replica both read from jax about the chip they
hold. Imports jax: never imported by the harness process."""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness import peaks

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def count_compiles() -> List[float]:
    """A list that grows by the seconds of every backend compilation in
    this process from now on."""
    import jax

    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(secs)
        if name == COMPILE_EVENT else None)
    return compiles


def device_facts() -> Dict[str, Any]:
    """Platform, kind, count and memory as jax reports them here; the peak
    is that of the fullest chip."""
    import jax

    devices = jax.devices()
    memory = [{k: (d.memory_stats() or {}).get(k) for k in MEMORY_KEYS}
              for d in devices]
    return {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory": memory,
        "memory_peak_bytes": max(
            int(m["peak_bytes_in_use"] or 0) for m in memory),
    }


def require_chips(facts: Dict[str, Any], chips: int) -> None:
    """No chip, fewer chips than the cell asks for, or a kind whose peaks
    are not in the table: the run fails here."""
    if facts["platform"] != "tpu" or facts["count"] != chips:
        raise RuntimeError(f"the cell asks for {chips} TPU chip(s), this "
                           f"process sees {facts}")
    peaks.peak(facts["kind"])
