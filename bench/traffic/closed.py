"""Closed loop: ``clients`` callers, each with one request of
``frames_per_request`` frames in flight, each sending its next request as
soon as its last one is answered, until the window closes. A request's
latency has no due time, so it runs from the moment it is sent.

One thread plays every caller: it waits for the answers in the order
the requests were sent, which is the order the Engine's FIFO queue serves
them in.
"""
from __future__ import annotations

import collections
import threading
import time

from loadgen import observe


def _drive(load, t0: float, t_end: float) -> None:
    time.sleep(max(0.0, t0 - time.perf_counter()))
    ring = collections.deque()
    for _ in range(load.traffic["clients"]):
        s = load.send(None)
        ring.append(s)
        load.sent.append(s)
    while ring:
        s = ring.popleft()
        observe(s, t_end)
        if time.perf_counter() < t_end:
            s2 = load.send(None)
            ring.append(s2)
            load.sent.append(s2)


def threads(load, t0: float, t_end: float) -> list:
    return [threading.Thread(target=_drive, args=(load, t0, t_end),
                             name="bench-client")]
