"""The benchmark's own arithmetic on samples, kept here so that no change
to the program can change the yardstick."""

from __future__ import annotations

import numpy as np


def percentile(samples, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between closest
    ranks (numpy's default method, as ``repro.obs.metrics.percentiles``
    computes it). NaN for an empty sample."""
    a = np.asarray(samples, np.float64).reshape(-1)
    if a.size == 0:
        return float("nan")
    return float(np.percentile(a, q))

