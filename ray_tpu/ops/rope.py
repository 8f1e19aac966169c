"""Rotary position embeddings (RoPE), precomputed frequencies + fused apply."""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def rope_frequencies(
    head_dim: int, max_seq: int, theta: float = 500000.0
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Return (cos, sin) tables of shape [max_seq, head_dim // 2], fp32."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    pos = jnp.arange(max_seq, dtype=jnp.float32)
    angles = jnp.outer(pos, inv_freq)  # [S, D/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Rotate ``x`` of shape [..., S, H, D] by position.

    ``cos``/``sin`` are [max_seq, D/2]; ``positions`` (optional, [..., S])
    selects rows, defaulting to arange(S). Split-halves convention.
    """
    seq = x.shape[-3]
    if positions is None:
        c = cos[:seq]
        s = sin[:seq]
        # broadcast over batch and heads: [S, 1, D/2]
        c = c[:, None, :]
        s = s[:, None, :]
    else:
        c = cos[positions][..., :, None, :]
        s = sin[positions][..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def yarn_inv_freq(
    rotary_dim: int, theta: float, factor: float, original_max: int,
    beta_fast: float, beta_slow: float,
) -> np.ndarray:
    """YaRN's frequencies (Peng et al. 2023, arXiv:2309.00071) for the
    ``rotary_dim // 2`` pairs of a head, float32: ``theta^(-2i / dim)``
    (kept: extrapolation) blended with that over ``factor``
    (interpolation) by a linear ramp over the pair index, 0 at the pair
    that makes ``beta_fast`` turns in ``original_max`` positions (floored)
    and 1 at the one that makes ``beta_slow`` (ceiled), both held inside
    ``[0, dim - 1]``: fast pairs keep their frequency, slow ones are
    stretched. Numbers of the config alone, so computed on the host."""
    pairs = np.arange(rotary_dim // 2, dtype=np.float64)
    kept = theta ** (-2.0 * pairs / rotary_dim)

    def pair_of(turns: float) -> float:
        return (
            rotary_dim * math.log(original_max / (turns * 2.0 * math.pi))
            / (2.0 * math.log(theta))
        )

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), rotary_dim - 1)
    ramp = np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (kept * (1.0 - ramp) + kept / factor * ramp).astype(np.float32)
