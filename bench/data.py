"""Inputs made from the seed: selection features.

Copied from the program's generator (``repro.data.datasets``
``GaussianMixtureDataset``) so that the yardstick does not move when it
changes.
"""
from __future__ import annotations

import numpy as np


def subseed(seed: int, *tags: int) -> int:
    """A 31-bit seed for JAX keys, derived from any whole ``seed`` and tags."""
    return int(np.random.default_rng([int(seed), *tags]).integers(0, 2**31 - 1))


def gaussian_mixture(seed: int, n_classes: int, per_class: int, dim: int, *,
                     tail_frac: float = 0.25, sep: float = 6.0):
    """Features with dense class cores and a tail drawn toward other classes.

    ``per_class`` rows of each class, in an order shuffled from the seed (as
    a real dataset's rows come), so a class's rows are scattered over the
    table; returns float32 features ``(n_classes * per_class, dim)`` and
    int64 labels.
    """
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_classes, dim)) * sep
    n_tail = int(per_class * tail_frac)
    n_core = per_class - n_tail
    x = np.empty((n_classes * per_class, dim), np.float32)
    for c in range(n_classes):
        lo = c * per_class
        x[lo:lo + n_core] = centers[c] + rng.normal(size=(n_core, dim))
        other = centers[(c + 1 + rng.integers(0, n_classes - 1, n_tail)) % n_classes]
        x[lo + n_core:lo + per_class] = (centers[c] * 0.55 + other * 0.45
                                         + rng.normal(size=(n_tail, dim)) * 1.5)
    y = np.repeat(np.arange(n_classes, dtype=np.int64), per_class)
    order = rng.permutation(len(y))
    return x[order], y[order]
