"""Every length the same: ``value``."""

import numpy as np


def draw(rng, spec: dict, n: int) -> np.ndarray:
    return np.full(n, spec["value"], np.int64)
