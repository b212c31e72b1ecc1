"""YaRN's scaling of a rotary table (Peng et al. 2023, arXiv:2309.00071):
the one place the program computes it, for every family whose
``rope_parameters`` say ``"yarn"`` — the one 64-wide key all heads of a
latent layer share (``models/kimi_k2.py``) and the whole heads of a
full-attention layer (``models/mellum.py``).  Which pairs a family turns
(adjacent or rotate-half) and what it multiplies its scores by are the
family's own.
"""

from __future__ import annotations

import math

import numpy as np


def yarn_ramp(dim: int, theta: float, original: int, beta_fast: float = 32.0,
              beta_slow: float = 1.0) -> tuple[int, int]:
    """``(lo, hi)``: the pairs of a ``dim``-wide rotation that make
    ``beta_fast`` / ``beta_slow`` turns over the ``original`` positions
    (floor and ceiling, kept inside the table): YaRN leaves the pairs
    below ``lo`` alone and slows those from ``hi`` on."""
    def pair(turns):
        return dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), dim // 2 - 1))


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0) -> tuple:
    """YaRN's ``dim / 2`` frequencies, float32: pair ``j`` keeps ``e_j =
    theta ** (-2j / dim)`` below ``lo``, turns ``factor`` times slower
    from ``hi`` on, and ramps linearly between — ``lo`` / ``hi`` the
    pairs that make ``beta_fast`` / ``beta_slow`` turns over the
    ``original`` positions (:func:`yarn_ramp`)."""
    lo, hi = yarn_ramp(dim, theta, original, beta_fast, beta_slow)
    j = np.arange(dim // 2, dtype=np.float32)
    e = np.float32(theta) ** (-2 * j / np.float32(dim))
    ramp = np.clip((j - lo) / max(hi - lo, 1e-3), 0, 1).astype(np.float32)
    return tuple(float(f) for f in
                 (e * (1 - ramp) + e / np.float32(factor) * ramp))


def yarn_attention_factor(factor: float, mscale: float = 1.0) -> float:
    """YaRN's ``m = 0.1 * mscale * ln(factor) + 1`` (1 where nothing is
    scaled): what a family multiplies its rotated queries and keys by,
    or, squared, its scores."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0
