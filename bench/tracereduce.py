"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
time and the longest idle gaps, all inside one marked window.

What it reads, as a TPU trace lays it out:

- device planes are named ``/device:TPU:<n>``; on each, the line
  ``XLA Ops`` holds one event per HLO operation that ran (a Pallas kernel
  is a custom call whose event name starts with ``%<kernel name>``), and
  the line ``XLA Modules`` one event per jitted program run;
- the window runs from the end of the first to the start of the last run
  of the jitted function named ``MARK``, which the benchmark dispatches
  at both ends of the traced window.

The trace is taken without host events: the host tracer records every
small transpose of the program's input transfer and slows the host
several fold, which would make the device look idler than it is. So an
idle gap is named by the program whose run ends it (``before
jit__lambda``: the host was preparing that run's launch).

Busy time is the union of the op intervals on a device, clipped to the
window, averaged over the devices that ran anything.
"""
from __future__ import annotations

import collections
import re

MARK = "bench_window_mark"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _label(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``;
    ``jit__lambda(8248...)`` -> ``jit__lambda``."""
    m = re.match(r"%?([^ =(]+)", name)
    return m.group(1) if m else name


class Reduced:
    """What one trace says, in seconds, for the window it marks."""

    def __init__(self, window, ops_by_device, modules_by_device):
        self.window = window  # (start_ns, end_ns)
        self.ops_by_device = ops_by_device  # device -> [(start, end, name)]
        self.modules_by_device = modules_by_device  # same, program runs

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clipped(self, dev):
        a, b = self.window
        return [
            (max(s, a), min(e, b), n)
            for s, e, n in self.ops_by_device[dev]
            if e > a and s < b
        ]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.ops_by_device:
            return 0.0
        tot = 0.0
        for dev in self.ops_by_device:
            tot += sum(e - s for s, e in _union((s, e) for s, e, _ in self._clipped(dev)))
        return tot / len(self.ops_by_device) / 1e9

    def op_seconds(self) -> dict:
        """Device seconds per op label, summed over devices."""
        acc = collections.Counter()
        for dev in self.ops_by_device:
            for s, e, n in self._clipped(dev):
                acc[_label(n)] += (e - s) / 1e9
        return dict(acc)

    def events(self, pattern: str) -> list:
        """Ops on any device whose name matches ``pattern`` and that start
        inside the window, whole: ``[(start_ns, end_ns, name)]``."""
        rx = re.compile(pattern)
        a, b = self.window
        return [
            ev for evs in self.ops_by_device.values() for ev in evs
            if a <= ev[0] < b and rx.search(ev[2])
        ]

    def idle_gaps(self, top: int = 10) -> list:
        """The ``top`` longest idle gaps on the first device, as
        ``[label, seconds]``, labelled by the program run that ends them."""
        if not self.ops_by_device:
            return []
        dev = sorted(self.ops_by_device)[0]
        busy = _union((s, e) for s, e, _ in self._clipped(dev))
        a, b = self.window
        edges = [a] + [x for iv in busy for x in iv] + [b]
        gaps = [
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        starts = sorted(
            (s, _label(n)) for s, _, n in self.modules_by_device.get(dev, [])
        )
        out = []
        for g0, g1 in gaps:
            nxt = next((n for s, n in starts if s >= g1), None)
            label = f"before {nxt}" if nxt and g1 < b else "until window end"
            out.append([label, (g1 - g0) / 1e9])
        return out


def read(path: str) -> Reduced:
    """Parse one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Reduced:
    ops, modules, marks = {}, {}, []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            if line.name == OPS_LINE and evs:
                ops[plane.name] = evs
            elif line.name == MODULES_LINE:
                modules[plane.name] = [
                    ev for ev in evs if not ev[2].startswith(f"jit_{MARK}")
                ]
                marks += [ev[:2] for ev in evs if ev[2].startswith(f"jit_{MARK}")]
    if len(marks) < 2:
        raise ValueError(f"the trace holds fewer than two {MARK!r} device runs")
    marks.sort()
    return Reduced((marks[0][1], marks[-1][0]), ops, modules)
