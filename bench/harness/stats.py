"""Percentile and rate arithmetic of the end-to-end metrics.

Percentiles are nearest-rank over ALL requests due in the window: a
request that failed, or never produced what the metric times, enters as
+inf, so it counts as a miss and can only raise a tail.
"""
from __future__ import annotations

import math

INF = float("inf")


def percentile(values, q: float) -> float:
    """Nearest-rank q-th percentile (0 < q <= 100); +inf entries sort
    last. An empty sample has no percentile (+inf)."""
    vals = sorted(values)
    if not vals:
        return INF
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])


def ttft_s(requests) -> list:
    """Due time to first emitted token, per attempted request."""
    return [r.first_token_t - r.arrival_t if r.first_token_t >= 0 and
            r.done else INF for r in requests]


def tpot_s(requests) -> list:
    """Each attempted request's mean gap between output tokens:
    (last - first emission) / (tokens - 1). A request that did not finish
    is a miss; one with a single token has no gap and is left out."""
    out = []
    for r in requests:
        if not r.done:
            out.append(INF)
        elif len(r.generated) > 1:
            out.append((r.last_token_t - r.first_token_t) /
                       (len(r.generated) - 1))
    return out


def rate(events, t0: float, t1: float) -> float:
    """Sum of counts of (time, count) events inside [t0, t1], per second."""
    return sum(c for t, c in events if t0 <= t <= t1) / (t1 - t0)
