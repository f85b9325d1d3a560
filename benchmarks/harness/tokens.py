"""Seeded token ids: the one generator every traffic kind draws from."""

from __future__ import annotations

import numpy as np


def rows(spec: dict, vocab: int, seed: int, count: int, length: int) -> np.ndarray:
    """``count`` rows of ``length`` int32 token ids from ``seed``.

    ``spec`` is the traffic file's ``tokens`` group. ``zipf``: id ``k``
    (0-based rank) with probability proportional to ``(k + 1) ** -a`` over
    the whole vocabulary — natural text's skew, so that the embedding
    gather and the loss see a few hot rows and a long tail."""
    rng = np.random.default_rng(seed)
    if spec["distribution"] == "zipf":
        weights = np.arange(1, vocab + 1, dtype=np.float64) ** -float(spec["a"])
        cdf = np.cumsum(weights / weights.sum())
        ids = np.searchsorted(cdf, rng.random((count, length)), side="right")
        return np.minimum(ids, vocab - 1).astype(np.int32)
    raise ValueError(f"unknown token distribution {spec['distribution']!r}")
