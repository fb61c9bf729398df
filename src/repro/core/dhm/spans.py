"""A preallocated log of host spans for the serving :class:`Engine`.

Each span is one row of six ``int64`` columns: ``kind`` (an index into
:data:`KINDS`), ``id``, ``parent`` (the row of the enclosing span, -1 for
none), ``start_ns``, ``end_ns`` and ``arg``. Times are
``time.perf_counter()`` in nanoseconds, the clock the Engine stamps
``submitted_at`` with.

Recording a span writes into the columns and creates no Python object
the garbage collector tracks, so an armed log does not bring on
collections of its own. Rows are handed out by an atomic counter, so
several threads may record at once without a lock; spans past
``capacity`` are counted in ``dropped`` and not written. While armed,
the log also records each garbage collection as a ``gc`` span (through
``gc.callbacks``).

Span kinds, with the parent each is recorded under:

- ``flush`` (id: flush number; arg: live requests): one flush.
- ``queued`` (id: request index; parent: the flush that took it): from
  ``submitted_at`` to the take.
- ``pack`` (parent: the group): building the group's host array, a
  copy of the requests' frames it spans, zero-padded past their end;
  no copy where the group is a view of one request's frames.
- ``group`` (id: group number within its flush; parent: the flush; arg:
  real frames, 0 for a launch dropped or handed to the serial retry): one
  micro-batch from its launch to the end of its finish, retries
  included. A flush keeps two groups in flight (it launches group k+1
  before it finishes group k), so consecutive groups' spans overlap. A
  group's time outside its children is what follows its check, the
  watchdog thread's join.
- ``stage`` (parent: the group): queuing the asynchronous host-to-device
  copy, in the launch.
- ``forward`` (parent: the group): from the closure's call in the launch
  to the logits being ready in the finish. It holds the copy itself, the
  next group's launch and the watchdog thread's start.
- ``fetch``, ``check`` (parent: the group): the device-to-host read of
  the logits, and ``isfinite`` on that host copy.
- ``retry`` (parent: the group): one backoff sleep.
- ``complete`` (parent: the flush): the scatter to requests.
- ``wait`` (arg: 0 queue empty, 1 not full and not due): the flush
  loop's wait on its condition.
- ``gc`` (arg: generation): one garbage collection.
"""
from __future__ import annotations

import gc
import itertools
import time

import numpy as np

KINDS = (
    "flush", "queued", "pack", "group", "stage", "forward", "check",
    "fetch", "complete", "retry", "wait", "gc",
)
(
    FLUSH, QUEUED, PACK, GROUP, STAGE, FORWARD, CHECK,
    FETCH, COMPLETE, RETRY, WAIT, GC,
) = range(len(KINDS))

COLUMNS = ("kind", "id", "parent", "start_ns", "end_ns", "arg")


class SpanLog:
    """Up to ``capacity`` spans in preallocated ``int64`` columns."""

    kinds = KINDS  # the name of each ``kind`` value

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.kind = np.full(capacity, -1, np.int64)
        self.id = np.zeros(capacity, np.int64)
        self.parent = np.full(capacity, -1, np.int64)
        self.start_ns = np.zeros(capacity, np.int64)
        self.end_ns = np.zeros(capacity, np.int64)
        self.arg = np.zeros(capacity, np.int64)
        self._next = itertools.count()  # next() is atomic under the GIL
        self._reserved = None  # rows handed out, fixed by close()
        self._gc_t0 = 0.0

    def begin(self, kind: int, id: int, parent: int, t0: float) -> int:
        """Open a span at ``t0`` (seconds); returns its row, or -1 when
        the log is full. Close it with :meth:`end`."""
        row = next(self._next)
        if row >= self.capacity:
            return -1
        self.id[row] = id
        self.parent[row] = parent
        self.start_ns[row] = t0 * 1e9
        self.end_ns[row] = -1
        self.kind[row] = kind
        return row

    def end(self, row: int, t1: float, arg: int = 0) -> None:
        if row >= 0:
            self.end_ns[row] = t1 * 1e9
            self.arg[row] = arg

    def add(
        self, kind: int, id: int, parent: int, t0: float, t1: float,
        arg: int = 0,
    ) -> int:
        """Record a whole span from ``t0`` to ``t1`` (seconds); returns
        its row, or -1 when the log is full."""
        row = self.begin(kind, id, parent, t0)
        self.end(row, t1, arg)
        return row

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.add(GC, 0, -1, self._gc_t0, time.perf_counter(),
                     info["generation"])

    def watch_gc(self) -> None:
        """Record every garbage collection as a ``gc`` span."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def unwatch_gc(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def close(self) -> "SpanLog":
        """Stop counting: fixes :attr:`n` and :attr:`dropped`. A span
        recorded later is not counted."""
        self.unwatch_gc()
        if self._reserved is None:
            self._reserved = next(self._next)
        return self

    def _closed(self) -> int:
        if self._reserved is None:
            raise RuntimeError("close the span log before reading its counts")
        return self._reserved

    @property
    def n(self) -> int:
        """Rows written: the spans are rows ``[0, n)``."""
        return min(self._closed(), self.capacity)

    @property
    def dropped(self) -> int:
        """Spans not written because the log was full."""
        return max(0, self._closed() - self.capacity)

    def columns(self) -> dict:
        """Column name -> the ``n`` written values."""
        n = self.n
        return {c: getattr(self, c)[:n] for c in COLUMNS}
