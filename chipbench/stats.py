"""Percentiles and spreads as the benchmark reports them."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(latencies: Sequence[float], q: float,
               offered: Optional[int] = None) -> float:
    """The ``q``-th percentile (0-100) of latency over ``offered`` queries.

    A query that was offered and has no latency (failed or dropped) counts
    as missing every limit: it sorts above every measured latency, as
    infinity. The percentile is the nearest rank, so a tail that lands on
    a missing query reads ``inf``."""
    vals = sorted(float(v) for v in latencies)
    n = len(vals) if offered is None else int(offered)
    if n < len(vals):
        raise ValueError(f"{len(vals)} latencies for {n} offered queries")
    if n == 0:
        raise ValueError("no query offered")
    vals += [math.inf] * (n - len(vals))
    rank = max(math.ceil(q / 100.0 * n), 1)
    return vals[rank - 1]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile, as a share of the
    median (``statistics.quantiles`` with n=4)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
