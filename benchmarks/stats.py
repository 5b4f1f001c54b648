"""Percentiles, and the rule for the highest one a sample can carry."""

from __future__ import annotations

from typing import Sequence

import numpy as np

#: the percentiles the rule chooses among
LADDER = (50.0, 90.0, 95.0, 99.0, 99.9)


def percentile(values: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def highest_percentile(samples: int, beyond: int = 10) -> float:
    """The highest percentile of LADDER that has at least `beyond`
    independent samples beyond it (choosing-metrics, section 1); 50 if
    none has. Every pod of a batch binds at the same instant, so the
    independent samples of a pod-to-bind latency are the window's
    rounds, not its pods."""
    best = LADDER[0]
    for q in LADDER:
        if round(samples * (100.0 - q) / 100.0, 6) >= beyond:
            best = q
    return best
