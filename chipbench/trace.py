"""Reduction of a profiler trace to what the per-layer metrics read.

A run with ``--trace 1`` records its measured window with
``jax.profiler`` inside a host span named :data:`WINDOW`.  This module
reads the ``.xplane.pb`` the profiler wrote (``jax.profiler.ProfileData``)
and keeps, clipped to that span:

* the device's operations: the ``XLA Ops`` line of each ``/device:``
  plane; where the trace has no device plane (a trace recorded on the
  CPU), the host events that carry an ``hlo_op`` stat;
* the host thread that ran the window, whose events say what the host
  was doing while the device sat idle.

Busy time is the union of the operations' intervals, per device,
averaged over the devices; idle is the rest of the window.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"


@dataclass
class Event:
    name: str
    start: float          # ns, on the trace's clock
    end: float
    stats: Dict[str, object] = field(default_factory=dict)
    self_ns: float = 0.0  # duration less the ops nested inside it

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9

    @property
    def op(self) -> str:
        """The HLO instruction's name: a TPU trace names an op by its whole
        instruction text (``%fusion.4 = f32[...] fusion(...)``)."""
        return self.name.split(" = ", 1)[0].lstrip("%")


def _self_times(evs: List[Event]) -> None:
    """Set each op's self time: a ``while`` or ``call`` op's event spans
    the events of the ops in its body, on the same line."""
    stack: List[Event] = []
    for e in sorted(evs, key=lambda e: (e.start, -e.end)):
        e.self_ns = e.end - e.start
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack:
            stack[-1].self_ns -= e.end - e.start
        stack.append(e)


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of ``[start, end)`` intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    """Device operations and host events of one traced window."""

    def __init__(self, planes, window: str = WINDOW):
        host_lines, dev_planes = [], defaultdict(list)
        for plane in planes:
            pname = plane.name
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                             _stats(e)) for e in line.events]
                if pname.startswith("/device:"):
                    if line.name == OPS_LINE:
                        dev_planes[pname].extend(evs)
                elif pname.startswith("/host:"):
                    host_lines.append(evs)
        spans = [e for line in host_lines for e in line if e.name == window]
        if not spans:
            raise ValueError(f"no host span named {window!r} in the trace")
        self.lo, self.hi = spans[0].start, spans[0].end
        if not dev_planes:   # a CPU trace: XLA's ops run on host threads
            cpu = [e for line in host_lines for e in line
                   if "hlo_op" in e.stats]
            if cpu:
                dev_planes["/host:CPU"] = cpu
        self.devices: Dict[str, List[Event]] = {
            p: sorted((e for e in evs if e.end > self.lo and e.start < self.hi),
                      key=lambda e: e.start)
            for p, evs in dev_planes.items()}
        for evs in self.devices.values():
            _self_times(evs)
        self.host = next(line for line in host_lines
                         if any(e is spans[0] for e in line))

    @classmethod
    def from_dir(cls, path: str, window: str = WINDOW) -> "Trace":
        from jax.profiler import ProfileData

        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        newest = max(files, key=os.path.getmtime)
        return cls(ProfileData.from_file(newest).planes, window)

    # -- time --------------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def ops(self) -> List[Event]:
        return [e for evs in self.devices.values() for e in evs]

    def _busy(self, evs: List[Event]) -> List[Tuple[float, float]]:
        return merge([(max(e.start, self.lo), min(e.end, self.hi))
                      for e in evs])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        per = [sum(e - s for s, e in self._busy(evs))
               for evs in self.devices.values()]
        return sum(per) / len(per) * 1e-9

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle stretch of the first device, longest first, named by
        the innermost host event that covers its middle."""
        if not self.devices:
            return []
        busy = self._busy(next(iter(self.devices.values())))
        edges = [self.lo] + [t for iv in busy for t in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        out = []
        for s, e in gaps:
            mid = (s + e) / 2
            cover = [h for h in self.host
                     if h.start <= mid < h.end and h.name != WINDOW]
            name = (min(cover, key=lambda h: h.end - h.start).name
                    if cover else "host: no event")
            out.append((name, (e - s) * 1e-9))
        return sorted(out, key=lambda g: -g[1])

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Self time per op, summed over its executions and averaged over
        the devices, the largest ``n``."""
        tot: Dict[str, float] = defaultdict(float)
        for e in self.ops():
            tot[e.op] += e.self_ns * 1e-9 / len(self.devices)
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def kernel(self, needle: str) -> Tuple[int, float]:
        """``(events, seconds)`` of the device ops named ``needle`` or
        ``needle.<n>`` (a Pallas call takes its kernel's Python name, e.g.
        ``divergence_sq.2``), averaged over the devices."""
        if not self.devices:
            return 0, 0.0
        count, secs = 0, 0.0
        for evs in self.devices.values():
            for e in evs:
                if e.op == needle or e.op.startswith(needle + "."):
                    count += 1
                    secs += e.seconds
        n = len(self.devices)
        return count // n, secs / n

    def breakdown(self) -> Dict[str, list]:
        return {"device_ops": [[k, v] for k, v in self.top_ops(10)],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:10]]}

