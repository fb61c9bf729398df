"""Open loop: arrivals on a schedule fixed before the window, each sent
whether or not earlier ones were answered. Latency runs from the
scheduled arrival, so a stall that delays later sends counts against
them, and each request records how late it was sent.

The schedule is a Poisson process at ``rate_per_s`` conditioned on its
count: ``round(rate_per_s * seconds)`` times drawn uniformly over the
window from the seed and sorted, so that seeds change the order of the
work and not its amount. One thread sends on the schedule and one waits
for the answers in the order they were sent.
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

from loadgen import observe


def schedule(traffic: dict, seconds: float, rng) -> np.ndarray:
    """Arrival offsets from the window's start, in seconds, sorted."""
    count = int(round(traffic["rate_per_s"] * seconds))
    return np.sort(rng.uniform(0.0, seconds, count))


def _send(load, t0: float, offsets, queue, done) -> None:
    for off in offsets:
        due = t0 + float(off)
        time.sleep(max(0.0, due - time.perf_counter()))
        s = load.send(due)
        with done:
            queue.append(s)
            done.notify()
    with done:
        queue.append(None)
        done.notify()


def _collect(load, t_end: float, queue, done) -> None:
    while True:
        with done:
            while not queue:
                done.wait()
            s = queue.popleft()
        if s is None:
            return
        load.sent.append(s)
        observe(s, t_end)


def threads(load, t0: float, t_end: float) -> list:
    offsets = schedule(load.traffic, t_end - t0, load.rng)
    queue, done = collections.deque(), threading.Condition()
    return [
        threading.Thread(target=_send, args=(load, t0, offsets, queue, done),
                         name="bench-sender"),
        threading.Thread(target=_collect, args=(load, t_end, queue, done),
                         name="bench-collector"),
    ]
