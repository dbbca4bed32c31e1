"""End-to-end statistics, taken over all the work of a window.

A tail is the tail of every request: a request that failed, was refused or
never produced what is timed counts as missing, an infinite time, so it
can only push the percentile up. ``percentile`` uses the nearest-rank
definition: the smallest value with at least p% of the sample at or below.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank p-th percentile; ``math.inf`` entries count as missing
    and sort last. Empty input gives ``math.inf``."""
    xs = sorted(values)
    if not xs:
        return math.inf
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[rank - 1]


def gaps(times: Sequence[float]) -> List[float]:
    return [b - a for a, b in zip(times, times[1:])]


def bursts(arrivals: Sequence[Tuple[float, int]], gap: float,
           longest: float) -> List[Tuple[float, float, int]]:
    """Group ``(time, tokens)`` arrivals, sorted by time, into bursts:
    ``(first, last, tokens)``. A burst ends at the first gap of at least
    ``gap`` seconds, or ``longest`` seconds after its first arrival, so a
    stream with no gaps is cut into pieces of that length."""
    out: List[Tuple[float, float, int]] = []
    for t, c in arrivals:
        if out and t - out[-1][1] < gap and t - out[-1][0] < longest:
            first, _, n = out[-1]
            out[-1] = (first, t, n + c)
        else:
            out.append((t, t, c))
    return out
