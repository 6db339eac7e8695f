"""Arrivals, percentiles and the pinned threshold policy."""
import math

import numpy as np
import pytest

from chipbench.arrivals import ArrivalTrace, poisson_arrivals
from chipbench.policy import PinnedThresholds, threshold_for_share
from chipbench.stats import percentile, spread


def test_arrivals_are_deterministic_per_seed():
    a = poisson_arrivals(50.0, 400, np.random.default_rng(2**31 + 7))
    b = poisson_arrivals(50.0, 400, np.random.default_rng(2**31 + 7))
    c = poisson_arrivals(50.0, 400, np.random.default_rng(2**31 + 8))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert len(a) == len(c) == 400
    assert np.all(np.diff(a) >= 0)


def test_arrivals_follow_the_rate():
    t = poisson_arrivals(40.0, 8000, np.random.default_rng(3))
    assert t[-1] == pytest.approx(200.0, rel=0.05)


def test_trace_replays_fixed_times():
    times = poisson_arrivals(10.0, 50, np.random.default_rng(1))
    tr = ArrivalTrace(times, 10.0)
    assert np.array_equal(tr.arrivals(np.random.default_rng(9)), times)
    assert tr.duration_s >= times[-1]
    assert tr.rate_at(0.5) == 10.0 and float(np.max(tr.qps)) == 10.0


def test_percentile_nearest_rank():
    lat = [float(i) for i in range(1, 101)]
    assert percentile(lat, 50) == 50.0
    assert percentile(lat, 95) == 95.0
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0


def test_failed_query_counts_as_missing():
    lat = [0.1] * 95
    assert percentile(lat, 95, offered=100) == 0.1
    assert math.isinf(percentile(lat, 96, offered=100))
    assert math.isinf(percentile([0.1] * 90, 95, offered=100))
    with pytest.raises(ValueError):
        percentile([0.1] * 3, 50, offered=2)


def test_spread_is_interquartile_over_median():
    assert spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    assert spread([9.0, 10.0, 10.0, 11.0]) == pytest.approx(
        (10.75 - 9.25) / 10.0)


def test_threshold_for_share():
    confs = np.linspace(0.0, 1.0, 1001)
    t = threshold_for_share(confs, 0.3)
    assert np.mean(confs < t) == pytest.approx(0.3, abs=0.002)
    assert threshold_for_share(confs, 0.0) == 0.0
    assert not np.any(confs < threshold_for_share(confs, 0.0))
    with pytest.raises(ValueError):
        threshold_for_share(confs, 1.5)


def test_pinned_thresholds_ignore_the_plan():
    class Plan:
        thresholds = (0.9, 0.1)
    pol = PinnedThresholds([0.4, 0.5])
    assert pol.select(Plan(), None) == (0.4, 0.5)
    with pytest.raises(ValueError):
        PinnedThresholds([0.4]).select(Plan(), None)
