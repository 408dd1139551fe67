"""Percentile and failure arithmetic, in one place.

A request that failed, was shed or did not finish in time has no latency;
it is ranked as slower than every finished request (``math.inf``), so a
tail that reaches into the failures is infinite and the run is not
correct. Nearest-rank percentiles: every reported value is a value that
was observed.
"""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float | None:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``;
    ``math.inf`` entries sort last. None for an empty sample."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def latency_percentile(latencies, q: float) -> tuple[float | None, bool]:
    """(percentile, finite?) over per-request latencies where a failed
    request is ``None``: failures rank slowest."""
    vals = [math.inf if v is None else float(v) for v in latencies]
    p = percentile(vals, q)
    return p, p is not None and math.isfinite(p)


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median — the spread the builder's contract sets bounds from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
