"""Rotary-embedding frequencies that more than one family shares.

YaRN (Peng et al. 2023) as HF ``_compute_yarn_parameters`` and DeepSeek's
``DeepseekV2YarnRotaryEmbedding`` both compute it: what the families differ in
is where the attention factor (``mscale``) goes — on cos and sin (Laguna) or
into the softmax scale (DeepSeek-V2) — so only the frequencies live here.
"""

from __future__ import annotations

import math

import numpy as np


def yarn_inv_freq(theta: float, dim: int, factor: float, original_max_position_embeddings: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0) -> np.ndarray:
    """Inverse frequencies ``[dim / 2]`` (float64) of a YaRN rope over ``dim``
    rotated lanes: interpolated (``1 / (factor * theta^(2i/dim))``) and
    extrapolated (``1 / theta^(2i/dim)``) frequencies blended by a linear ramp
    between the dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    the original context."""
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(original_max_position_embeddings
                               / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention factor ``0.1 * mscale * ln(factor) + 1`` (1.0 at a
    factor of 1 or below)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0
