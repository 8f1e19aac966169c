"""Clipped log-normal lengths: ``median``, ``sigma``, ``lo``, ``hi``,
and optionally ``snap`` (round up to a multiple of it, still at most
``hi``)."""

import math

import numpy as np


def draw(rng, spec: dict, n: int) -> np.ndarray:
    values = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    out = np.clip(np.rint(values), spec["lo"], spec["hi"]).astype(np.int64)
    snap = spec.get("snap", 1)
    if snap > 1:
        out = np.minimum(-(-out // snap) * snap, spec["hi"])
    return out
