"""End-to-end arithmetic, from the request log alone.

* ``placements_per_s``: placements of all jobs whose completion the
  client observed inside [t0, t1), over the whole window.  Jobs in
  flight at either edge belong to the side on which they complete.
* ``eval_p50_ms`` / ``eval_p95_ms``: over ALL requests due inside
  [t0, t1), completion observed minus the instant the submit was due.
  A request that failed, was shed or never ended counts as beyond any
  percentile.
"""
from __future__ import annotations

import math

BEYOND_MS = 1.0e9  # what a percentile that lands on a failed request reads


def placements_per_s(requests, t0: float, t1: float) -> float:
    placed = sum(r.placements for r in requests if r.ok and t0 <= r.done < t1)
    return placed / (t1 - t0)


def window_completions(requests, t0: float, t1: float) -> list:
    return [r for r in requests if r.done and t0 <= r.done < t1]


def due_in_window(requests, t0: float, t1: float) -> list:
    return [r for r in requests if t0 <= r.due < t1]


def percentile(values, pct: float) -> float:
    """Nearest-rank percentile: the smallest value with at least
    ``pct`` percent of the sample at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no samples")
    rank = max(1, math.ceil(pct / 100.0 * len(ordered)))
    return ordered[rank - 1]


def latencies_ms(requests) -> list:
    """Due-time latency of each request; a failed one is infinite."""
    return [
        (r.done - r.due) * 1000.0 if r.ok else math.inf for r in requests
    ]


def latency_percentile_ms(requests, pct: float) -> float:
    value = percentile(latencies_ms(requests), pct)
    return BEYOND_MS if math.isinf(value) else value


def lateness_ms(requests) -> list:
    """How late the generator sent each request (send minus due)."""
    return [(r.sent - r.due) * 1000.0 for r in requests if r.sent]


def longest_gaps(completions, t0: float, t1: float) -> list:
    """(offset from t0, seconds) of the gaps between consecutive
    completions in the window, edges included, longest first."""
    times = sorted(r.done for r in completions)
    points = [t0] + times + [t1]
    gaps = [(a - t0, b - a) for a, b in zip(points, points[1:])]
    gaps.sort(key=lambda g: -g[1])
    return gaps


def spread(values) -> float:
    """Interquartile range over the median, the contract's spread."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
