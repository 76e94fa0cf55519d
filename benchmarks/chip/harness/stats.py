"""Statistics over every sample of a window.  No statistic here is taken
from medians of chunks or of steps: a tail is the tail of all samples."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float) -> float | None:
    """``q``-th percentile of all ``values`` (linear interpolation between
    order statistics, numpy's default); None when there are none."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def token_gaps(times: dict, lo: float, hi: float) -> list[float]:
    """Seconds between consecutive tokens of one request, for every request
    in ``times`` (rid -> host arrival times of its tokens, in order), where
    both tokens reached the host inside ``[lo, hi]``."""
    out = []
    for ts in times.values():
        t = np.asarray(ts, np.float64)
        t = t[(t >= lo) & (t <= hi)]
        out.extend(np.diff(t).tolist())
    return out


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, q2, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / q2
