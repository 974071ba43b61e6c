"""Reduce a profiler trace (``.xplane.pb``) to the device metrics.

* busy time: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices;
* idle gaps: the complement of that union inside the window, each named
  by the innermost span of the program's tracer (``repro.obs.trace``)
  open on the host at the gap's midpoint;
* per-operation device time, and per-kernel time for the events whose
  name is a Pallas kernel's ``name``.

Host and device clocks are put on one axis by a marker: the run opens a
``TraceAnnotation`` named ``MARKER`` and reads its own clock inside it, so
the marker's start in the trace and that clock reading are one instant.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

MARKER = "chipbench.window_open"
#: the line of a TPU device plane that holds one event per executed op
OP_LINE = "XLA Ops"


def op_name(event_name: str) -> str:
    """The op's name: a TPU op event is named by its HLO instruction
    (``%price_bundle.1 = f32[8,65536]... custom-call(...)``); keep
    ``price_bundle.1``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals (start, end) into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] between disjoint sorted busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclass
class DeviceEvents:
    """Op events of the devices, (name, start_ns, end_ns), and the marker's
    start on the same clock."""

    per_device: Dict[str, List[Tuple[str, float, float]]]
    marker_ns: Optional[float]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, device_prefix: str = "/device:TPU:",
         op_line: Callable[[str], bool] = lambda n: n == OP_LINE) -> DeviceEvents:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    per_device: Dict[str, List[Tuple[str, float, float]]] = {}
    marker = None
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            evs = per_device.setdefault(plane.name, [])
            lines = list(plane.lines)
            if not any(op_line(line.name) for line in lines):
                raise ValueError(f"{plane.name} has no op line: "
                                 f"{[line.name for line in lines]}")
            for line in lines:
                if op_line(line.name):
                    for ev in line.events:
                        evs.append((op_name(ev.name), float(ev.start_ns),
                                    float(ev.start_ns + ev.duration_ns)))
        if marker is None and plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER:
                        marker = float(ev.start_ns)
                        break
    return DeviceEvents(per_device, marker)


@dataclass
class Reduction:
    window_s: float
    busy_s: float                         # averaged over devices
    op_s: Dict[str, float]                # summed over devices
    op_count: Dict[str, int]
    gaps: List[Tuple[float, float]]       # host-clock seconds, device 0

    def kernel(self, name: str) -> Tuple[float, int]:
        """(seconds, launches) of the events named ``name`` or whose name
        starts with it (XLA may suffix a custom call's name)."""
        s = sum(v for k, v in self.op_s.items() if k == name or k.startswith(name + "."))
        n = sum(v for k, v in self.op_count.items() if k == name or k.startswith(name + "."))
        return s, n


def reduce(dev: DeviceEvents, host_open: float, host_close: float,
           marker_host: float) -> Reduction:
    """Reduce the op events that fall in the window [host_open,
    host_close] (host clock, seconds); ``marker_host`` is the host clock
    read inside the marker annotation."""
    if dev.marker_ns is None:
        raise ValueError(f"the trace holds no {MARKER!r} marker")
    if not dev.per_device:
        raise ValueError("the trace holds no device plane")
    off = dev.marker_ns - marker_host * 1e9          # ns = host_s * 1e9 + off
    lo, hi = host_open * 1e9 + off, host_close * 1e9 + off
    busy_total = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    first_gaps: List[Interval] = []
    for i, (name, evs) in enumerate(sorted(dev.per_device.items())):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if min(e, hi) > max(s, lo)]
        merged = union((s, e) for _, s, e in inside)
        busy_total += sum(e - s for s, e in merged)
        for n, s, e in inside:
            op_s[n] += (e - s) * 1e-9
            op_n[n] += 1
        if i == 0:
            first_gaps = [((s - off) * 1e-9, (e - off) * 1e-9)
                          for s, e in gaps(merged, lo, hi)]
    ndev = len(dev.per_device)
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy_total / ndev * 1e-9,
                     op_s=dict(op_s), op_count=dict(op_n), gaps=first_gaps)


def name_gaps(gap_list: Sequence[Interval], spans: Sequence[tuple],
              top: int = 10) -> List[list]:
    """Idle seconds per innermost host span open at each gap's midpoint,
    the ``top`` largest. ``spans`` are (name, start_s, end_s, depth) on
    the host clock; a gap with no span open is named ``engine``."""
    # one sweep over span starts, gap midpoints and span ends in time
    # order; the spans nest, so the open ones form a stack whose top is
    # the innermost
    points = []
    for i, (name, s, e, _) in enumerate(spans):
        points.append((s, 0, i))
        points.append((e, 2, i))
    for i, (g0, g1) in enumerate(gap_list):
        points.append((0.5 * (g0 + g1), 1, i))
    points.sort()
    stack: List[int] = []
    acc: Dict[str, float] = defaultdict(float)
    for _, kind, i in points:
        if kind == 0:
            stack.append(i)
        elif kind == 2:
            for j in range(len(stack) - 1, -1, -1):
                if stack[j] == i:
                    del stack[j]
                    break
        else:
            g0, g1 = gap_list[i]
            acc[spans[stack[-1]][0] if stack else "engine"] += g1 - g0
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]
