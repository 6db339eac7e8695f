"""Open-loop arrivals for a cell, made from the run's seed.

A copy of the Poisson arithmetic of ``serving/trace.py`` (``Trace.arrivals``
over a ``static_trace``): each second draws a Poisson count at the rate,
and the count's arrival times are uniform within the second. The copy
stops after a fixed number of arrivals, so every seed offers the same
number of queries.
"""
from __future__ import annotations

import numpy as np


def poisson_arrivals(rate_qps: float, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    """The first ``n`` arrival times (seconds, sorted) of a Poisson
    process at ``rate_qps``."""
    if rate_qps <= 0 or n < 1:
        raise ValueError(f"need a positive rate and count, got "
                         f"{rate_qps} qps and {n} arrivals")
    times, sec = [], 0
    while sum(len(t) for t in times) < n:
        k = rng.poisson(rate_qps)
        times.append(np.sort(sec + rng.random(k)))
        sec += 1
    return np.concatenate(times)[:n]


class ArrivalTrace:
    """The trace object ``ClusterBackend.serve`` replays: fixed arrival
    times, the per-second rate the planner may read, and the duration."""

    def __init__(self, times: np.ndarray, rate_qps: float,
                 name: str = "chipbench"):
        self.times = np.asarray(times, dtype=float)
        self.name = name
        self.qps = np.full(max(int(np.ceil(self.times[-1])), 1),
                           float(rate_qps))

    @property
    def duration_s(self) -> float:
        return float(len(self.qps))

    def rate_at(self, t: float) -> float:
        return float(self.qps[min(max(int(t), 0), len(self.qps) - 1)])

    def arrivals(self, rng=None) -> np.ndarray:
        """The fixed arrival times; the backend's own generator is not
        drawn from."""
        return self.times.copy()
