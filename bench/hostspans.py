"""The Engine's own host measurements, read for the benchmark: its phase
counters (``EngineStats``), and its span log put on the device trace's
clock so that device idle time is named by what the host was doing.

Clock. The Engine stamps its spans with ``time.perf_counter``; the
profiler stamps device events on a clock of its own. The benchmark
brackets each of the two window marks' dispatches with
``time.perf_counter_ns()`` just before and just after the call, which
waits for the mark to run. Each mark's device run lies inside its host
bracket, so the offset (host minus device) lies in
``[before - run start, after - run end]`` for each mark. The first and the
last mark must allow a common offset; the middle of the range they share
maps device time to host time, and its width (``clock_bracket_us``) bounds
how far a mapped time can be off.

Attribution. An idle interval of the device is named by the Engine span
(any kind but ``queued``, which is a request's wait and not the host's
work) that holds most of it in its own time, its time outside its
children: so a gap that spans a flush's check, fetch and the next stage
is named by whichever of those took most of it, and ``flush`` or
``group`` names what the flush loop or the watchdog thread did between
them. Of spans that hold it equally, the shortest names it. A gap that
no span covers keeps its trace label alone.
"""
from __future__ import annotations

import numpy as np

import tracereduce
from pct import nearest_rank

# The rows of ``breakdown.flush_phases``, in order; ``<kind> self`` is a
# span's time outside its children.
PHASES = (
    "pack", "stage", "forward", "check", "fetch", "complete", "retry",
    "group self", "flush self", "wait", "gc",
)


def per_batch_ms(ctx, field: str):
    """The Engine counter ``field`` (seconds) over the untraced part of
    the window, in milliseconds per dispatched micro-batch. None where
    the program keeps no such counter or dispatched nothing."""
    st = ctx.host_stats
    value = getattr(st, field, None)
    if value is None or not st.n_batches:
        return None
    return value / st.n_batches * 1e3


def mark_runs(pd) -> list:
    """The window mark's device runs on the first device, in order:
    ``[(start_ns, end_ns)]``."""
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == tracereduce.MODULES_LINE:
                return sorted(
                    (e.start_ns, e.end_ns) for e in line.events
                    if e.name.startswith(f"jit_{tracereduce.MARK}")
                )
    return []


def clock_offset(stamps, marks) -> tuple:
    """``(offset_ns, width_ns)``: host clock minus device clock, from the
    host brackets ``stamps`` ``[(before_ns, after_ns)]`` around the first
    and last window mark and the mark's device runs ``marks``. Raises
    ``ValueError`` where the two marks allow no common offset."""
    pairs = [(stamps[0], marks[0]), (stamps[-1], marks[-1])]
    lo = max(before - start for (before, _), (start, _) in pairs)
    hi = min(after - end for (_, after), (_, end) in pairs)
    if lo > hi:
        raise ValueError(
            f"the first and last window marks disagree on the clock offset "
            f"by {lo - hi:.0f} ns"
        )
    return (lo + hi) / 2, hi - lo


def idle_intervals(reduced, top=None) -> list:
    """The ``top`` (all where None) longest idle intervals of the first
    device inside the window, longest first: ``[(start_ns, end_ns,
    label)]``, labelled as ``Reduced.idle_gaps`` labels them."""
    if not reduced.ops_by_device:
        return []
    dev = sorted(reduced.ops_by_device)[0]
    busy = tracereduce._union((s, e) for s, e, _ in reduced._clipped(dev))
    a, b = reduced.window
    edges = [a] + [x for iv in busy for x in iv] + [b]
    gaps = [
        (edges[i], edges[i + 1])
        for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    starts = sorted(
        (s, tracereduce._label(n))
        for s, _, n in reduced.modules_by_device.get(dev, [])
    )
    out = []
    for g0, g1 in gaps:
        nxt = next((n for s, n in starts if s >= g1), None)
        label = f"before {nxt}" if nxt and g1 < b else "until window end"
        out.append((g0, g1, label))
    return out


class Mapped:
    """The spans of one closed ``SpanLog`` on the device clock, one entry
    per row of the log."""

    def __init__(self, log, offset_ns: float):
        self.kinds = log.kinds
        cols = log.columns()
        self.kind, self.arg, self.parent = cols["kind"], cols["arg"], cols["parent"]
        self.start = cols["start_ns"] - offset_ns
        self.end = cols["end_ns"] - offset_ns
        # Written, closed, and the host's own work.
        self.host = (
            (self.kind >= 0) & (self.end >= self.start)
            & (self.kind != self.kinds.index("queued"))
        )

    def name(self, i: int) -> str:
        name = self.kinds[int(self.kind[i])]
        if name == "wait":
            return "wait (empty)" if self.arg[i] == 0 else "wait (not full)"
        if name == "gc":
            return f"gc (generation {int(self.arg[i])})"
        return name

    def covering(self, g0: float, g1: float):
        """The name of the host span that holds most of ``[g0, g1)`` in
        its own time, or None where no span overlaps it."""
        over = np.minimum(self.end, g1) - np.maximum(self.start, g0)
        over = np.where(self.host, np.maximum(over, 0.0), 0.0)
        if not len(over) or over.max() <= 0:
            return None
        inner = np.zeros(len(over))
        child = np.flatnonzero(self.host & (self.parent >= 0))
        np.add.at(inner, self.parent[child], over[child])
        own = over - inner
        best = np.flatnonzero(own == own.max())
        return self.name(best[np.argmin((self.end - self.start)[best])])

    def covered_ns(self, intervals) -> float:
        """Nanoseconds of ``intervals`` that some host span covers."""
        spans = tracereduce._union(
            (s, e) for s, e in zip(self.start[self.host], self.end[self.host])
            if e > s
        )
        total, j = 0.0, 0
        for g0, g1 in sorted((g0, g1) for g0, g1, *_ in intervals):
            while j < len(spans) and spans[j][1] <= g0:
                j += 1
            k = j
            while k < len(spans) and spans[k][0] < g1:
                total += min(spans[k][1], g1) - max(spans[k][0], g0)
                k += 1
        return total


def label_gaps(reduced, mapped: Mapped, top: int = 10) -> list:
    """``Reduced.idle_gaps(top)`` with the covering span's name in front
    of each label: ``[label, seconds]``."""
    out = []
    for g0, g1, label in idle_intervals(reduced, top):
        name = mapped.covering(g0, g1)
        out.append([f"{name}, {label}" if name else label, (g1 - g0) / 1e9])
    return out


def idle_attributed_pct(reduced, mapped: Mapped):
    """Share of the window's device idle time, in percent, that some
    Engine host span covers; None where the device was never idle."""
    gaps = idle_intervals(reduced)
    idle = sum(g1 - g0 for g0, g1, _ in gaps)
    if idle <= 0:
        return None
    return 100.0 * mapped.covered_ns(gaps) / idle


def forward_contained_pct(reduced, mapped: Mapped):
    """Share, in percent, of the ``forward`` spans inside the window that
    contain a whole device run of a program (``XLA Modules``) after the
    mapping; None where the window holds no forward span."""
    a, b = reduced.window
    fwd = np.flatnonzero(
        (mapped.kind == mapped.kinds.index("forward"))
        & (mapped.start >= a) & (mapped.end <= b)
    )
    if not len(fwd):
        return None
    dev = sorted(reduced.ops_by_device)[0]
    runs = np.array(
        [(s, e) for s, e, _ in reduced.modules_by_device.get(dev, [])]
    ).reshape(-1, 2)
    hit = sum(
        bool(((runs[:, 0] >= mapped.start[i]) & (runs[:, 1] <= mapped.end[i])).any())
        for i in fwd
    )
    return 100.0 * hit / len(fwd)


def flush_phases(log) -> list:
    """``[[phase, ms per micro-batch, max ms], ...]`` over the spans of
    ``log``: each kind's summed duration over the number of ``group``
    spans, and its longest span; ``group self`` and ``flush self`` are
    the time of those spans outside their children."""
    cols = log.columns()
    kind, parent = cols["kind"], cols["parent"]
    dur = (cols["end_ns"] - cols["start_ns"]).astype(np.float64)
    ok = (kind >= 0) & (dur >= 0)
    child = np.zeros(len(kind))
    sub = np.flatnonzero(ok & (parent >= 0) & (kind != log.kinds.index("queued")))
    np.add.at(child, parent[sub], dur[sub])
    n_groups = int(np.count_nonzero(ok & (kind == log.kinds.index("group"))))
    if not n_groups:
        return []
    out = []
    for phase in PHASES:
        name, _, self_time = phase.partition(" ")
        rows = np.flatnonzero(ok & (kind == log.kinds.index(name)))
        if not len(rows):
            continue
        d = dur[rows] - child[rows] if self_time else dur[rows]
        out.append([phase, float(d.sum()) / n_groups / 1e6, float(d.max()) / 1e6])
    return out


def queue_wait_ms(log):
    """Nearest-rank p95 of the ``queued`` spans, in milliseconds; None
    where there are none."""
    cols = log.columns()
    q = cols["kind"] == log.kinds.index("queued")
    return nearest_rank(list((cols["end_ns"][q] - cols["start_ns"][q]) / 1e6), 95.0)


def mapped(ctx):
    """The context's spans on its trace's clock, with the offset's width
    in nanoseconds: ``(Mapped, width_ns)``; ``(None, None)`` where the
    run armed no spans or took no trace."""
    log = getattr(ctx, "spans", None)
    if log is None or ctx.trace is None:
        return None, None
    offset, width = clock_offset(ctx.mark_stamps, ctx.mark_runs)
    return Mapped(log, offset), width
