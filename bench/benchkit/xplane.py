"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists (``Trace``); everything else works on those lists, so the tests
build small traces by hand.  Times are nanoseconds on the trace's clock.

* The window is the host span named ``WINDOW_SPAN`` that the harness opens
  at the window's start and closes at its end.
* A device's busy time is the union of the intervals in which an XLA
  program (the ``XLA Modules`` line; the ``XLA Ops`` line where a device
  has no module line) ran on it, clipped to the window; the idle share is
  one minus busy over the window, averaged over the devices.
* Program time by name sums module events whose name starts with a given
  prefix (``jit_scan`` ...), clipped to the window.
* The breakdown lists the operations that took the most device time, and
  the longest idle gaps labelled by the innermost host span that covers
  the middle of each gap.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

__all__ = ["Event", "Trace", "WINDOW_SPAN", "load", "find_xplane",
           "union_length", "busy_intervals", "window", "idle_share",
           "program_time", "program_calls", "top_ops", "idle_gaps"]

WINDOW_SPAN = "bench:window"


@dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    # per device plane: {"modules": [Event], "ops": [Event]}
    devices: Dict[str, Dict[str, List[Event]]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    key = "modules" if line.name == "XLA Modules" else "ops"
                    lines[key] = [Event(e.name, e.start_ns, e.end_ns)
                                  for e in line.events]
            if lines:
                tr.devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(Event(e.name, e.start_ns, e.end_ns)
                               for e in line.events if e.duration_ns > 0)
    return tr


def window(tr: Trace) -> Tuple[float, float]:
    spans = [e for e in tr.host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w = max(spans, key=lambda e: e.end - e.start)
    return w.start, w.end


def _clip(events: List[Event], lo: float, hi: float) -> List[Tuple[float,
                                                                  float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def _device_events(lines: Dict[str, List[Event]]) -> List[Event]:
    return lines.get("modules") or lines.get("ops") or []


def busy_intervals(tr: Trace, lo: float, hi: float,
                   device: Optional[str] = None) -> List[Tuple[float,
                                                               float]]:
    """Merged busy intervals of one device (the first one by default)."""
    name = device or sorted(tr.devices)[0]
    out = []
    for a, b in sorted(_clip(_device_events(tr.devices[name]), lo, hi)):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_seconds(tr: Trace) -> float:
    """Busy seconds in the window, averaged over the device planes."""
    lo, hi = window(tr)
    if not tr.devices:
        return 0.0
    return sum(union_length(_clip(_device_events(lines), lo, hi))
               for lines in tr.devices.values()) / len(tr.devices) / 1e9


def window_seconds(tr: Trace) -> float:
    lo, hi = window(tr)
    return (hi - lo) / 1e9


def idle_share(tr: Trace) -> Optional[float]:
    """Idle share of the window in percent; None without device planes."""
    if not tr.devices:
        return None
    return 100.0 * (1.0 - busy_seconds(tr) / window_seconds(tr))


def program_time(tr: Trace, prefix: str) -> float:
    """Seconds of device time of the programs whose module name starts
    with ``prefix``, in the window, summed over the devices."""
    lo, hi = window(tr)
    return sum(b - a for lines in tr.devices.values()
               for a, b in _clip([e for e in lines.get("modules", [])
                                  if e.name.startswith(prefix)], lo, hi)
               ) / 1e9


def program_calls(tr: Trace, prefix: str) -> int:
    """Module events named ``prefix...`` that start inside the window."""
    lo, hi = window(tr)
    return sum(1 for lines in tr.devices.values()
               for e in lines.get("modules", [])
               if e.name.startswith(prefix) and lo <= e.start < hi)


def top_ops(tr: Trace, k: int = 10) -> List[list]:
    """The ``k`` device operations with the most time in the window, in
    seconds summed over the devices (module events stand in for a device
    without an op line)."""
    lo, hi = window(tr)
    tot: Dict[str, float] = {}
    for lines in tr.devices.values():
        evs = lines.get("ops") or lines.get("modules") or []
        for e in evs:
            a, b = max(e.start, lo), min(e.end, hi)
            if b > a:
                tot[e.name] = tot.get(e.name, 0.0) + (b - a) / 1e9
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])
            [:k]]


def idle_gaps(tr: Trace, k: int = 10) -> List[list]:
    """The ``k`` longest idle gaps of the first device in the window, each
    labelled by the innermost host span over its middle."""
    if not tr.devices:
        return []
    lo, hi = window(tr)
    busy = busy_intervals(tr, lo, hi)
    gaps, prev = [], lo
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in tr.host if e.name != WINDOW_SPAN]
    out = []
    for a, b in gaps[:k]:
        mid = 0.5 * (a + b)
        cover = [e for e in host if e.start <= mid <= e.end]
        label = (min(cover, key=lambda e: e.end - e.start).name
                 if cover else "no host span")
        out.append([label, (b - a) / 1e9])
    return out
