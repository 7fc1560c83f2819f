"""Rotary frequencies under YaRN: the one copy `ops/mla.py` (adjacent
pairs) and `ops/attention.py` (half against half) both rotate by."""
from __future__ import annotations

import math

import numpy as np


def yarn_frequencies(rotary_dim: int, theta: float, factor: float = 1.0,
                     original_max: int = 4096, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> np.ndarray:
    """Rotation per position of each of the `rotary_dim / 2` channel
    pairs, float64: the published YaRN blend of the extrapolated
    (`theta^(-2i/d)`) and the interpolated (`/ factor`) frequencies over
    a linear ramp between the pairs that turn `beta_fast` and
    `beta_slow` times in the original context.  `factor` <= 1 is plain
    RoPE."""
    d = rotary_dim
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if factor <= 1:
        return extra

    def pair_of(turns):
        return (d * math.log(original_max / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)
