"""The threshold policy of every cell: each boundary's threshold pinned
where the cell's deferral share puts it.

The control plane's solver still plans workers and batch sizes each tick;
only the thresholds it would set are replaced. The share, not the
threshold value, is the traffic's fixed property: ``calibrate`` reads
each boundary's threshold off the discriminator's confidences on seeded
outputs of that boundary's tier, during set-up.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def threshold_for_share(confidences: Sequence[float], share: float) -> float:
    """The threshold below which ``share`` of ``confidences`` lie (a query
    defers when its confidence is below the threshold). A share of 0
    gives 0.0, under every confidence."""
    if not 0.0 <= share <= 1.0:
        raise ValueError(f"deferral share {share} outside [0, 1]")
    if share == 0.0:
        return 0.0
    if share == 1.0:
        return float("inf")
    return float(np.quantile(np.asarray(confidences, dtype=float), share))


class PinnedThresholds:
    """``ThresholdPolicy`` of ``serving/controlplane.py``: the same
    thresholds at every tick, whatever the plan proposes."""

    def __init__(self, thresholds: Sequence[float]):
        self.values: Tuple[float, ...] = tuple(float(t) for t in thresholds)

    def select(self, plan, telemetry) -> Tuple[float, ...]:
        if len(plan.thresholds) != len(self.values):
            raise ValueError(f"{len(self.values)} pinned thresholds for a "
                             f"plan with {len(plan.thresholds)} boundaries")
        return self.values
