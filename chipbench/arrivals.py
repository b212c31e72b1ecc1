"""Open-loop arrival times for a window, from a seed.

A Poisson process that had ``n`` arrivals in ``[0, seconds)`` puts them
at ``n`` independent uniform times (the construction of
``defer_tpu/serve/arrivals.py``, with the count fixed instead of drawn).
Fixing the count is what lets every run of a cell offer the same load.
"""

from __future__ import annotations

import numpy as np


def arrival_times(n: int, seconds: float, rng) -> np.ndarray:
    """``n`` sorted arrival offsets in ``[0, seconds)``."""
    return np.sort(rng.uniform(0.0, float(seconds), n))
