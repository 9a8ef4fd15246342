"""Percentiles from raw samples (no histogram buckets)."""
from __future__ import annotations

import math


def percentile(samples, q: float) -> float:
    """The ``q``-th percentile (0–100) of ``samples`` by linear
    interpolation between closest ranks (numpy's default rule)."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
