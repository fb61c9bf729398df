"""The chip's peaks and the roofline, kept with the benchmark, apart from
the program, so the yardstick cannot move with the code it measures.

A model's operations and bytes, computed from its shapes, are its
family's (``families/<name>.py``: ``frame_ops``, and the pricing of any
kernel it has a metric for); nothing here imports the program.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's peaks by ``device_kind``; an unknown kind is an error,
    never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]


def peak_ops_per_s(device_kind: str, config: dict) -> float:
    """The peak that prices a configuration: the key its file names
    (``peak``), read from the table for this device kind."""
    return float(peaks(device_kind)[config["peak"]])


def roofline_s(ops: float, n_bytes: float, device_kind: str, config: dict):
    """The least time the chip could take: ``max(ops / peak, bytes /
    bandwidth)``. Returns ``(seconds, bound)`` with bound ``"compute"``
    or ``"memory"``."""
    t_ops = ops / peak_ops_per_s(device_kind, config)
    t_mem = n_bytes / float(peaks(device_kind)["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
