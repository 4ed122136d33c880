"""Data sets of the chip benchmark, made on the host from a seed.

The generators are copies of ``repro.svm.data.gaussian_blobs`` and
``multiclass_blobs``: the benchmark owns its inputs and takes nothing from
the program under test.  A configuration names its generator, the
generator's arguments and a fixed ``data_seed``; the run's ``--seed``
permutes the rows.  Every seed therefore solves the same problem in
another order, so the work of a run does not change with its seed beyond
the solver's own sensitivity to row order (ties in the first selections).
"""

from __future__ import annotations

import numpy as np


def gaussian_blobs(n: int, seed: int, d: int, sep: float):
    """Two spherical Gaussians, means ``+-sep/2`` on the first axis;
    labels +-1 with equal probability."""
    rng = np.random.default_rng(seed)
    y = np.where(rng.uniform(size=n) < 0.5, 1.0, -1.0)
    mean = np.zeros((n, d))
    mean[:, 0] = y * sep / 2.0
    X = mean + rng.normal(size=(n, d))
    return X, y


def multiclass_blobs(n: int, seed: int, k: int, d: int, sep: float):
    """``k`` spherical Gaussians with centres on a circle of diameter
    ``sep`` in the first two axes; integer labels ``0..k-1``."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, k, size=n)
    theta = 2.0 * np.pi * y / k
    centers = np.zeros((n, d))
    centers[:, 0] = sep / 2.0 * np.cos(theta)
    centers[:, 1] = sep / 2.0 * np.sin(theta)
    X = centers + rng.normal(size=(n, d))
    return X, y.astype(np.int64)


GENERATORS = {"gaussian_blobs": gaussian_blobs,
              "multiclass_blobs": multiclass_blobs}


def make(config: dict, seed: int, l: int | None = None):
    """``(X, y)`` of ``config`` with its rows permuted by ``seed``.

    ``X`` is float32, the precision the solver runs in; ``y`` keeps the
    generator's labels (+-1 floats or integer classes).  ``l`` overrides
    the row count (small rehearsals only).
    """
    n = config["l"] if l is None else l
    gen = GENERATORS[config["generator"]]
    X, y = gen(n, seed=config["data_seed"], d=config["d"],
               **config["generator_args"])
    perm = np.random.default_rng(seed).permutation(n)
    y = y[perm]
    return np.ascontiguousarray(X[perm], np.float32), (
        y.astype(np.float32) if y.dtype.kind == "f" else y)
