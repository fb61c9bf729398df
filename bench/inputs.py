"""Frames and the streams weights and traffic are drawn from, made from
``--seed`` and nothing else.

One seed gives the same weights, frames and arrival schedule on every
run. Weights are made from the ``weights`` stream by the configuration's
family (``families/<name>.py``, ``make_params``), on the device; frames
are made on the host, where the program's ``Engine.submit`` takes them.
"""
from __future__ import annotations

import numpy as np


def streams(seed: int) -> dict:
    """Independent generators for weights, frames and traffic, from one
    seed of any size (``SeedSequence`` takes integers wider than 64 bits)."""
    ss = np.random.SeedSequence(seed)
    w, f, t = ss.spawn(3)
    return {
        "weights": w.generate_state(2, np.uint32),
        "frames": np.random.default_rng(f),
        "traffic": np.random.default_rng(t),
    }


def frame_pool(shape: tuple, grid_bits: int, n: int, rng) -> np.ndarray:
    """``n`` frames of ``shape`` (H, W, C), the family's ``frame_shape``,
    as (n, H, W, C) float32 on the input grid Q(b, b - 2): standard normal
    values rounded to the grid step and clipped to the signed ``b``-bit
    range, as a camera's quantized pixels would arrive."""
    step = 2.0 ** (grid_bits - 2)
    codes = np.round(rng.standard_normal((n, *shape), np.float32) * step)
    lim = 2 ** (grid_bits - 1)
    return (np.clip(codes, -lim, lim - 1) / step).astype(np.float32)
