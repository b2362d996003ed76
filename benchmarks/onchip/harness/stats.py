"""The arithmetic from timestamps to the numbers reported."""

from __future__ import annotations

import math
import statistics
from typing import Sequence

SEGMENTS = 5


def segment_rates(boundaries: Sequence[float], work: Sequence[float],
                  segments: int = SEGMENTS) -> list[float]:
    """Work per second in ``segments`` consecutive runs of whole steps.

    ``boundaries`` are the n+1 host timestamps that bound n steps and
    ``work[i]`` is what step i did (tokens). The steps are cut into segments of
    equal step count (a remainder at the end is left out); each segment's rate
    is its work over its boundary-to-boundary seconds. A diagnostic only: the
    rate reported is :func:`window_rate`."""
    n = len(boundaries) - 1
    if n != len(work):
        raise ValueError(f"{len(boundaries)} boundaries bound {n} steps, got {len(work)} work items")
    per = n // segments
    if per < 1:
        raise ValueError(f"{n} whole steps cannot fill {segments} segments")
    out = []
    for s in range(segments):
        a, b = s * per, (s + 1) * per
        out.append(sum(work[a:b]) / (boundaries[b] - boundaries[a]))
    return out


def window_rate(boundaries: Sequence[float], work: Sequence[float]) -> float:
    """All the work of the n whole steps over all their time: the sum of
    ``work`` over last boundary minus first. This is the end-to-end rate: a
    stall inside the window lowers it by the stall's share of the window.
    (:func:`segment_rates` of the same steps is printed beside it, to show
    where in the window a low reading came from.)"""
    if len(boundaries) - 1 != len(work) or not work:
        raise ValueError(f"{len(boundaries)} boundaries do not bound {len(work)} steps")
    return sum(work) / (boundaries[-1] - boundaries[0])


def percentile(values: Sequence[float], q: float) -> float:
    """q in [0, 100], linear interpolation between order statistics; a missed
    request is passed in as ``math.inf`` and lands in the tail."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if xs[hi] == math.inf:
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the median
    (the spread the bounds are set from)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
