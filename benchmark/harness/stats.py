"""The arithmetic of the end-to-end metrics: quantiles over every
request of the window, a rate over the whole window's seconds."""

from __future__ import annotations

from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order
    statistics; of one value, that value. No request is dropped."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 0.5)


def rate(units: float, window_s: float) -> float:
    """Work over the WHOLE window: a stall makes the window longer or
    the work less, so the rate falls with it."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return units / window_s

