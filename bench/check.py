"""The comparison that decides ``correct``.

Every request answered in the window is compared, frame by frame, with
the plain reference (``reference.py``) on the same pool frames. Two
numbers, each against a limit that the configuration file states:

- ``logit_gap``: the widest gap between a served logit and the
  reference's, over every compared frame, as a share of the largest
  reference logit.
- ``frames_off_pct``: the share of compared frames, in percent, that have
  some logit off the reference by more than ``OFF`` of that scale.

Besides, no request may go unanswered (``unanswered``, limit 0): an error
answer is a failed request, counted as such; an answer that never comes
is a wrong one. The limits and the readings they were set from are in
``PERF.md``.
"""
from __future__ import annotations

import numpy as np

OFF = 2.0**-10


def numbers(served: np.ndarray, ref: np.ndarray) -> dict:
    """The compared numbers of served logits against reference logits,
    row for row. A non-finite served value reads as an infinite gap."""
    scale = max(float(np.abs(ref).max()), 1e-30) if ref.size else 1.0
    diff = np.abs(served.astype(np.float64) - ref.astype(np.float64))
    diff[~np.isfinite(diff)] = np.inf
    gap = diff.max(axis=1) / scale if diff.size else np.zeros(0)
    return {
        "logit_gap": float(gap.max()) if gap.size else 0.0,
        "frames_off_pct": 100.0 * float(np.mean(gap > OFF)) if gap.size else 0.0,
    }


def verdict(nums: dict, limits: dict) -> tuple:
    """``(correct, checks)``: ``checks`` maps each number to its value
    and limit; correct when none is over its limit."""
    checks = {
        k: {"value": nums[k], "limit": limits[k]} for k in limits if k in nums
    }
    ok = len(checks) == len(limits) and all(
        c["value"] <= c["limit"] for c in checks.values()
    )
    return ok, checks
