"""The benchmark's own data: covtype-shaped rows and their split.

A copy of ``repro.data.synthetic.covtype_like`` and ``train_test_split``
(the program's generator is sound; the yardstick keeps its own copy so that
no later change to the program can change what is measured).  One change:
the mixture's geometry (means, covariances, label noise draws, order) comes
from ``geometry_seed`` alone, so the data set is a fixed deployment, as the
real UCI table is; ``--seed`` picks folds, waves, coefficients and arrivals.
"""
from __future__ import annotations

import numpy as np


def covtype_like(n: int, d: int, n_classes: int, seed: int,
                 label_noise: float = 0.08, n_modes: int = 6,
                 sample_seed: int | None = None):
    """Hard overlapping mixture: each class a mixture of anisotropic
    Gaussians whose modes interleave.  With ``sample_seed=None`` the draws
    are the program's copy's; otherwise the mixture (means, covariances)
    comes from ``seed`` and the points, label noise and order from
    ``sample_seed``: a fresh sample of one fixed population."""
    rng = np.random.default_rng(seed)
    rs = rng if sample_seed is None else np.random.default_rng(sample_seed)
    xs, ys = [], []
    per = n // (n_classes * n_modes)
    for c in range(n_classes):
        for _ in range(n_modes):
            mean = rng.normal(0, 1.6, d)
            a = rng.normal(0, 1, (d, d)) / np.sqrt(d)
            cov_half = 0.55 * a + 0.45 * np.eye(d)
            xs.append(rs.normal(size=(per, d)) @ cov_half.T + mean)
            ys.append(np.full(per, c))
    x = np.concatenate(xs).astype(np.float32)
    y = np.concatenate(ys).astype(np.int32)
    flip = rs.uniform(size=len(y)) < label_noise
    y = np.where(flip, rs.integers(0, n_classes, len(y)), y).astype(np.int32)
    p = rs.permutation(len(x))
    return x[p], y[p]


def train_test_split(x: np.ndarray, y: np.ndarray, test_frac: float,
                     seed: int):
    rng = np.random.default_rng(seed)
    p = rng.permutation(len(x))
    n_test = int(len(x) * test_frac)
    te, tr = p[:n_test], p[n_test:]
    return x[tr], y[tr], x[te], y[te]


def binary_rows(data: dict, sample_seed: int | None = None):
    """``data`` is a configuration's ``data`` block (its ``geometry_seed``
    fixes the population).  Returns (x_train, y_train, x_test, y_test),
    labels in {-1, +1} as float32."""
    seed = data["geometry_seed"]
    x, yc = covtype_like(n=data["n_rows"], d=data["n_features"],
                         n_classes=2, seed=seed,
                         label_noise=data["label_noise"],
                         n_modes=data["n_modes"], sample_seed=sample_seed)
    y = np.where(yc == 0, -1.0, 1.0).astype(np.float32)
    if data.get("test_frac", 0.0) > 0.0:
        return train_test_split(x, y, data["test_frac"], seed)
    return x, y, x[:0], y[:0]


def seeds(seed: int, n: int) -> list:
    """``n`` independent 31-bit seeds from any whole ``--seed``."""
    return [int(s) for s in np.random.default_rng(seed % 2**63).integers(0, 2**31 - 1, n)]
