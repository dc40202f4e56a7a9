"""Percentile arithmetic of the benchmark's own."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile with linear interpolation between order
    statistics (numpy's default, as the engine's own p50/p99 use)."""
    v = np.asarray(values, dtype=np.float64)
    if v.size == 0:
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(v, q))

