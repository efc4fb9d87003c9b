"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the metrics read.

Only the process that holds the chip can trace it, so this runs in the
worker or replica, on the file that process wrote, and what goes back to
the harness is the small dict ``reduce_trace`` returns.

The interval functions at the top are plain Python and are tested on
hand-made intervals; ``reduce_trace`` is tested on a trace recorded on the
chip (``benchmark/fixtures/``).

What the reduction calls what:

- a device plane is a plane named ``/device:TPU:<n>``; its operations are
  the events of its ``XLA Ops`` line (the line of HLO instructions; the
  ``XLA Modules`` and ``Steps`` lines cover the same time again and are
  left out);
- busy time is the union of those events' intervals; an idle gap is a
  stretch between two merged busy intervals;
- an operation's time is its *self* time: a ``while`` (a scanned layer
  stack) contains its body's operations on the same line, and counting
  both would count the body twice;
- a host span is an event on a host plane whose name starts with
  ``bench:`` (``jax.profiler.TraceAnnotation`` in the benchmark's own
  loop); a gap is labelled with the span that overlaps it most.

In a CPU rehearsal there is no device plane. ``rehearsal=True`` takes the
CPU backend's HLO events from the host plane instead, so that the control
flow can be walked; nothing read that way is ever reported as a device
number.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]            # (start_s, end_s)
Event = Tuple[str, float, float]          # (name, start_s, end_s)

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MOSAIC_PREFIX = "tpu_custom_call:"
COVERING_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                  "Framework Name Scope", "Source code", ASYNC_LINE,
                  "TC Overlay")
HOST_SPAN_PREFIX = "bench:"


# ------------------------------------------------------------ intervals
def merge_intervals(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, non-overlapping union of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_seconds(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge_intervals(intervals))


def idle_gaps(intervals: Iterable[Interval], t0: Optional[float] = None,
              t1: Optional[float] = None) -> List[Interval]:
    """The stretches of [t0, t1] that no interval covers. Without t0/t1
    only the gaps between the first start and the last end."""
    merged = merge_intervals(intervals)
    if not merged:
        return [(t0, t1)] if t0 is not None and t1 is not None and t1 > t0 \
            else []
    gaps: List[Interval] = []
    if t0 is not None and merged[0][0] > t0:
        gaps.append((t0, merged[0][0]))
    for (_, e), (s, _) in zip(merged, merged[1:]):
        gaps.append((e, s))
    if t1 is not None and merged[-1][1] < t1:
        gaps.append((merged[-1][1], t1))
    return gaps


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """(name, self seconds) per event of one line: an event's duration
    less what the events nested inside it cover."""
    order = sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1])))
    out: List[List] = []           # [name, self]
    stack: List[Tuple[float, int]] = []   # (end, index into out)
    for name, s, e in order:
        while stack and s >= stack[-1][0]:
            stack.pop()
        dur = max(0.0, e - s)
        if stack:  # nested: the parent loses what this one covers
            parent_end, pi = stack[-1]
            out[pi][1] -= max(0.0, min(e, parent_end) - s)
        out.append([name, dur])
        stack.append((e, len(out) - 1))
    return [(n, max(0.0, t)) for n, t in out]


def sum_by_name(pairs: Iterable[Tuple[str, float]]
                ) -> Dict[str, List[float]]:
    """name -> [seconds, count]."""
    acc: Dict[str, List[float]] = {}
    for name, t in pairs:
        row = acc.setdefault(name, [0.0, 0])
        row[0] += t
        row[1] += 1
    return acc


def label_gaps(gaps: Sequence[Interval], spans: Sequence[Event],
               top: int = 5, between: str = "between_steps"
               ) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps as (label, seconds). A gap is labelled with
    the host span that covers most of it; what no span covers counts for
    ``between`` (the host was in none of the benchmark's spans), and a
    trace without any span gives ``unattributed``."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        if not spans:
            out.append(("unattributed", e - s))
            continue
        cover: Dict[str, float] = {}
        for name, ss, se in spans:
            ov = min(e, se) - max(s, ss)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        cover[between] = max(0.0, (e - s) - busy_seconds(
            (max(s, ss), min(e, se)) for _, ss, se in spans))
        out.append((max(cover, key=cover.get), e - s))
    return out


# ---------------------------------------------------------------- traces
def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def short_name(text: str) -> str:
    """The TPU's trace names an operation by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``): keep the instruction's name,
    and mark a Pallas (Mosaic) kernel by its custom-call target, which is
    the only thing that tells it from XLA's own custom calls."""
    if not text.startswith("%"):
        return text
    name = text[1:].split(" = ", 1)[0]
    if 'custom_call_target="tpu_custom_call"' in text:
        return MOSAIC_PREFIX + name
    return name


def _events(events) -> List[Event]:
    return [(short_name(ev.name), ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in events]


def read_planes(path: str, *, rehearsal: bool = False):
    """(device -> op events, device -> asynchronous op events, host spans,
    summary of the planes and lines found)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    asyncs: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    summary: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        lines = list(plane.lines)
        summary[plane.name] = {}
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        has_ops_line = any(ln.name == OPS_LINE for ln in lines)
        for ln in lines:
            evs = list(ln.events)
            summary[plane.name][ln.name] = len(evs)
            if is_device:
                take = (ln.name == OPS_LINE) if has_ops_line \
                    else ln.name not in COVERING_LINES
                if take:
                    devices.setdefault(plane.name, []).extend(_events(evs))
                elif ln.name == ASYNC_LINE:
                    asyncs.setdefault(plane.name, []).extend(_events(evs))
                continue
            for ev in evs:
                if ev.name.startswith(HOST_SPAN_PREFIX):
                    s = ev.start_ns * 1e-9
                    spans.append((ev.name[len(HOST_SPAN_PREFIX):], s,
                                  s + ev.duration_ns * 1e-9))
                elif rehearsal and not ev.name.startswith("end: ") \
                        and any(k == "hlo_op" for k, _ in ev.stats):
                    s = ev.start_ns * 1e-9
                    devices.setdefault("rehearsal:cpu", []).append(
                        (ev.name, s, s + ev.duration_ns * 1e-9))
    return devices, asyncs, spans, summary


def reduce_trace(path: str, *, rehearsal: bool = False,
                 top_ops: int = 400) -> Dict:
    """The small dict the readers work from. Seconds throughout.

    ``busy_s``: union of operation intervals, averaged over the device
    planes. ``span_s``: first operation start to last operation end over
    all devices. ``ops``: [name, self seconds, count], summed over the
    devices and divided by their number, longest first. ``gaps``: the
    five longest idle gaps of the first device, labelled.
    """
    devices, asyncs, spans, summary = read_planes(path, rehearsal=rehearsal)
    if not devices:
        return {"n_devices": 0, "busy_s": 0.0, "span_s": 0.0, "ops": [],
                "async_ops": [], "gaps": [], "host_spans": {},
                "planes": summary}
    n = len(devices)
    busy = [busy_seconds((s, e) for _, s, e in evs)
            for evs in devices.values()]
    t0 = min(s for evs in devices.values() for _, s, _ in evs)
    t1 = max(e for evs in devices.values() for _, _, e in evs)
    ops: Dict[str, List[float]] = {}
    for evs in devices.values():
        for name, (t, c) in sum_by_name(self_times(evs)).items():
            row = ops.setdefault(name, [0.0, 0])
            row[0] += t / n
            row[1] += c / n
    first = devices[sorted(devices)[0]]
    gaps = label_gaps(idle_gaps((s, e) for _, s, e in first), spans)
    host = {name: [t, c] for name, (t, c) in sum_by_name(
        (name, e - s) for name, s, e in spans).items()}
    ranked = sorted(ops.items(), key=lambda kv: -kv[1][0])[:top_ops]
    in_flight = sum_by_name((name, e - s) for evs in asyncs.values()
                            for name, s, e in evs)
    return {
        "n_devices": n,
        "busy_s": sum(busy) / n,
        "busy_s_per_device": busy,
        "span_s": t1 - t0,
        "ops": [[name, t, c] for name, (t, c) in ranked],
        "async_ops": [[name, t / n, c / n] for name, (t, c) in sorted(
            in_flight.items(), key=lambda kv: -kv[1][0])[:top_ops]],
        "gaps": [[label, d] for label, d in gaps],
        "host_spans": host,
        "planes": summary,
    }


def profile_options():
    """Tracing that keeps TraceAnnotation spans and drops Python frames."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
