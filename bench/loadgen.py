"""The load generator. A traffic file (``traffic/<name>.json``) is data:
the parameters of one mix. Its ``loop`` names the module that drives it,
``traffic/<loop>.py``, found by name like every other piece
(``registry.traffic_loop``); a new mix is a new data file, and a new way
of sending is a new loop module beside the others, never an edit here.

A loop module has ``threads(load, t0, t_end)``: the threads, not yet
started, that send the mix's requests through ``load.send`` from ``t0``
on, stop sending at ``t_end``, and wait for each answer with
``observe``. The loops kept here:

- ``closed`` (``traffic/closed.py``): callers that each keep one request
  in flight.
- ``open`` (``traffic/open.py``): arrivals on a schedule fixed before the
  window, sent whether or not earlier ones were answered.

Every request names the frames it carries by their first index in the
frame pool, so that its answer can be checked afterwards. Timestamps are
``time.perf_counter()`` on the benchmark's own threads.
"""
from __future__ import annotations

import time

import registry

GRACE_S = 60.0  # how long past the window an answer may still come


class Sent:
    """One request: when it was due, sent and seen answered, and which
    pool frames it carries."""

    __slots__ = ("due", "sent", "seen", "first", "n", "req", "ok", "logits")

    def __init__(self, due, first, n):
        self.due, self.first, self.n = due, first, n
        self.sent = self.seen = None
        self.req = None
        self.ok = False
        self.logits = None


def observe(s: Sent, t_end: float) -> None:
    """Wait for one answer and stamp when the benchmark saw it. An error
    answer (shed, deadline, failure) is seen too, with ``ok`` false; an
    answer that never comes, ``GRACE_S`` past ``t_end``, leaves ``seen``
    unset."""
    timeout = GRACE_S + max(0.0, t_end - time.perf_counter())
    try:
        s.logits = s.req.result(timeout=timeout)
        s.ok = True
    except TimeoutError:
        return
    except Exception:  # noqa: BLE001 - any structured error is a failed request
        s.ok = False
    s.seen = time.perf_counter()
    s.req = None


def request_starts(traffic: dict, rng):
    """Pool index of each request slot's first frame, in a seeded order.
    Requests take whole, non-overlapping slices of the pool in turn."""
    n = traffic["frames_per_request"]
    slots = traffic["pool_frames"] // n
    return rng.permutation(slots) * n


class Load:
    """Drives one traffic mix against ``submit`` (the program's
    ``Engine.submit``) over ``[t0, t_end)`` on the threads of its loop,
    and keeps every request sent in ``sent``."""

    def __init__(self, traffic: dict, submit, pool, starts, rng,
                 bench=registry.BENCH):
        self.traffic, self.submit, self.pool = traffic, submit, pool
        self.starts, self.rng, self.bench = starts, rng, bench
        self.sent: list = []
        self._threads: list = []
        self._k = 0

    def send(self, due) -> Sent:
        """Send the next request, due at ``due`` (None: due when sent),
        with the next slice of the pool in the seeded order."""
        first = int(self.starts[self._k % len(self.starts)])
        self._k += 1
        s = Sent(due, first, self.traffic["frames_per_request"])
        frames = self.pool[s.first : s.first + s.n]
        s.sent = time.perf_counter()
        s.req = self.submit(frames[0] if s.n == 1 else frames)
        if s.due is None:
            s.due = s.sent
        return s

    def start(self, t0: float, seconds: float) -> None:
        """Start the mix's loop at ``t0`` (perf_counter) for ``seconds``."""
        loop = registry.traffic_loop(self.traffic["loop"], self.bench)
        self._threads = loop.threads(self, t0, t0 + seconds)
        for t in self._threads:
            t.start()

    def join(self) -> None:
        """Wait until every request sent has been answered or has timed
        out ``GRACE_S`` past the window."""
        for t in self._threads:
            t.join()
