"""Build the last line of a run and hold it to the contract before it is printed.

The line is one JSON object with the keys ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, optionally ``breakdown``;
nothing else goes in it. ``metrics`` gives every metric of the cell for this
kind of run as ``{"value": number, "unit": unit}``: the end-to-end metrics
untraced, the per-layer metrics traced (the end-to-end ones ride along).
``device`` gives ``platform``, ``kind``, ``count``, ``memory_peak_bytes``
and, traced, ``window_s`` and ``busy_s`` with ``0 < busy_s <= window_s``.
A violation is a ``LastLineError``: the run exits non-zero with the reason
and prints no last line.
"""

from __future__ import annotations

import json
import math
import re
from typing import Any, Dict, List, Optional

from benchmark.harness.loader import manifest_cell, manifest_metrics

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("window_s", "busy_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
MAX_BREAKDOWN = 10


class LastLineError(Exception):
    """The would-be last line breaks the contract."""


def _is_number(x: Any) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def required_metrics(manifest: Dict[str, Any], cell_name: str,
                     trace: bool) -> Dict[str, str]:
    """name -> unit of what the line must give for this cell and run."""
    group = "per_layer" if trace else "end_to_end"
    return {m["name"]: m["unit"]
            for m in manifest_metrics(manifest, cell_name, group)}


def build(manifest: Dict[str, Any], cell_name: str, trace: bool, *,
          values: Dict[str, Optional[float]], device: Dict[str, Any],
          correct: bool, attempted: int, failed: int,
          breakdown: Optional[Dict[str, List]] = None) -> Dict[str, Any]:
    """Pick the cell's metrics out of ``values`` (a value of None is a
    reader that found nothing: the metric is left out, and ``validate``
    then says which one is missing)."""
    units = dict(required_metrics(manifest, cell_name, trace))
    if trace:  # the end-to-end metrics ride along in a traced run
        for name, unit in required_metrics(manifest, cell_name, False).items():
            units.setdefault(name, unit)
    metrics = {name: {"value": values[name], "unit": unit}
               for name, unit in units.items()
               if values.get(name) is not None}
    keys = DEVICE_KEYS + (TRACED_DEVICE_KEYS if trace else ())
    line: Dict[str, Any] = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": {k: device[k] for k in keys if k in device},
    }
    if trace and breakdown is not None:
        line["breakdown"] = {
            k: [list(e) for e in breakdown.get(k, [])[:MAX_BREAKDOWN]]
            for k in BREAKDOWN_KEYS}
    return line


def violations(line: Any, manifest: Dict[str, Any], cell_name: str,
               trace: bool) -> List[str]:
    """Every way in which ``line`` breaks the contract ([] if none)."""
    out: List[str] = []
    if not isinstance(line, dict):
        return ["the line is not a JSON object"]
    cell = manifest_cell(manifest, cell_name)
    if cell is None:
        return [f"BENCHMARK.json lists no workload {cell_name!r}"]
    allowed = set(TOP_KEYS) | ({"breakdown"} if trace else set())
    for k in TOP_KEYS:
        if k not in line:
            out.append(f"key {k!r} is missing")
    for k in line:
        if k not in allowed:
            out.append(f"key {k!r} does not belong in the line")
    if out:
        return out
    if not isinstance(line["correct"], bool):
        out.append("'correct' is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) \
                or line[k] < 0:
            out.append(f"{k!r} is not a count")
    if not out and line["failed"] > line["attempted"]:
        out.append("more failed than attempted")
    if not out and line["attempted"] < 1:
        out.append("nothing was attempted")

    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        out.append("'metrics' is not an object")
        metrics = {}
    for name, unit in required_metrics(manifest, cell_name, trace).items():
        if name not in metrics:
            out.append(f"metric {name!r} of this cell is missing")
    known = {m["name"]: m["unit"]
             for g in ("end_to_end", "per_layer") for m in manifest[g]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for name, entry in metrics.items():
        if not isinstance(name, str) or not NAME_RE.match(name):
            out.append(f"metric name {name!r} is outside the allowed form")
            continue
        if name not in known:
            out.append(f"metric {name!r} is not in BENCHMARK.json")
            continue
        if not isinstance(entry, dict) or set(entry) != {"value", "unit"}:
            out.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if not isinstance(entry["unit"], str) \
                or not UNIT_RE.match(entry["unit"]):
            out.append(f"metric {name!r}: unit {entry['unit']!r} is outside "
                       "the allowed form (1 to 16 of A-Za-z0-9_/%.-)")
        elif entry["unit"] != known[name]:
            out.append(f"metric {name!r}: unit {entry['unit']!r}, "
                       f"BENCHMARK.json says {known[name]!r}")
        if not _is_number(entry["value"]):
            out.append(f"metric {name!r}: value {entry['value']!r} is not "
                       "a finite number")
        elif name in e2e and entry["value"] <= 0:
            out.append(f"end-to-end metric {name!r} is {entry['value']}: "
                       "it must be above 0")
        elif ("roofline" in name or "mfu" in name) \
                and entry["value"] > 105.0:
            out.append(f"metric {name!r} is {entry['value']}% of a peak: "
                       "operations counted too high or time too short")

    dev = line["device"]
    if not isinstance(dev, dict):
        return out + ["'device' is not an object"]
    want = DEVICE_KEYS + (TRACED_DEVICE_KEYS if trace else ())
    for k in want:
        if k not in dev:
            out.append(f"device.{k} is missing")
    for k in dev:
        if k not in DEVICE_KEYS + TRACED_DEVICE_KEYS:
            out.append(f"device.{k} does not belong in the line")
    for k in ("platform", "kind"):
        if k in dev and (not isinstance(dev[k], str) or not dev[k]):
            out.append(f"device.{k} is not a name")
    if "count" in dev and (not isinstance(dev["count"], int)
                           or dev["count"] != cell["chips"]):
        out.append(f"device.count is {dev.get('count')!r}, the cell asks "
                   f"for {cell['chips']}")
    if "memory_peak_bytes" in dev and (
            not isinstance(dev["memory_peak_bytes"], int)
            or dev["memory_peak_bytes"] <= 0):
        out.append("device.memory_peak_bytes is not a positive byte count")
    if trace and all(k in dev for k in TRACED_DEVICE_KEYS):
        w, b = dev["window_s"], dev["busy_s"]
        if not (_is_number(w) and _is_number(b)):
            out.append("device.window_s and busy_s must be numbers")
        elif not 0 < b <= w:
            out.append(f"device.busy_s {b} is not in (0, window_s {w}]")

    if "breakdown" in line:
        bd = line["breakdown"]
        if not isinstance(bd, dict) or set(bd) != set(BREAKDOWN_KEYS):
            out.append("'breakdown' must hold device_ops and idle_gaps")
        else:
            for k in BREAKDOWN_KEYS:
                rows = bd[k]
                if not isinstance(rows, list) or len(rows) > MAX_BREAKDOWN:
                    out.append(f"breakdown.{k} is not a list of at most "
                               f"{MAX_BREAKDOWN}")
                    continue
                for row in rows:
                    if not (isinstance(row, list) and len(row) == 2
                            and isinstance(row[0], str)
                            and _is_number(row[1])):
                        out.append(f"breakdown.{k} entry {row!r} is not "
                                   "[name, seconds]")
                        break
    return out


def validate(line: Any, manifest: Dict[str, Any], cell_name: str,
             trace: bool) -> None:
    found = violations(line, manifest, cell_name, trace)
    if found:
        raise LastLineError("; ".join(found))


def dumps(line: Dict[str, Any]) -> str:
    """One line, numbers with all their digits."""
    return json.dumps(line, separators=(", ", ": "), allow_nan=False)
