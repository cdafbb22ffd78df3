"""The benchmark's regression data: YearPredictionMSD-shaped rows.

UCI's YearPredictionMSD table (515,345 songs x 90 timbre features: 12
means and 78 covariances; target the release year, 1922-2011, skewed
towards the 2000s) does not ship with the repository, so
:func:`year_like` draws a population of its shape from ``geometry_seed``:

* features: an anisotropic Gaussian mixture in the style of
  ``data.covtype_like`` (modes with random means and mixed covariances);
* target: a smooth nonlinear function of the features (a sum of random
  cosine ridges, one per hidden direction), standardised, plus Gaussian
  noise of ``noise`` standard deviations, then mapped by rank onto a
  left-skewed year distribution: ``2011 - floor(age)``, ``age`` the
  Weibull(``shape``, ``scale``) quantile of the row's rank, cut at 89.

The split is the data set's own: the first ``n_train`` rows train, the
rest test.  :func:`regression_rows` returns the training target minus the
training split's mean year, the one-line preprocessing a user of a
least-squares SVM without an offset applies.
"""
from __future__ import annotations

import numpy as np

FIRST_YEAR, LAST_YEAR = 1922, 2011


def year_like(n: int, d: int, seed: int, n_modes: int = 8,
              noise: float = 1.0, n_ridges: int = 16, shape: float = 1.2,
              scale: float = 14.0):
    """(x (n, d) float32, year (n,) float32 in [1922, 2011]), rows in a
    seeded random order; everything comes from ``seed``."""
    rng = np.random.default_rng(seed)
    sizes = np.full(n_modes, n // n_modes)
    sizes[: n % n_modes] += 1
    xs = []
    for per in sizes:
        mean = rng.normal(0, 1.6, d)
        a = rng.normal(0, 1, (d, d)) / np.sqrt(d)
        cov_half = 0.55 * a + 0.45 * np.eye(d)
        xs.append(rng.normal(size=(per, d)) @ cov_half.T + mean)
    x = np.concatenate(xs)
    x = x[rng.permutation(n)]
    w = rng.normal(0, 1, (d, n_ridges)) / np.sqrt(d)
    phase = rng.uniform(0, 2 * np.pi, n_ridges)
    amp = rng.normal(0, 1, n_ridges)
    f = np.cos(x @ w / 2.0 + phase) @ amp
    z = (f - f.mean()) / f.std() + noise * rng.normal(size=n)
    u = (np.argsort(np.argsort(z)) + 0.5) / n
    age = np.minimum(scale * (-np.log1p(-u)) ** (1.0 / shape),
                     LAST_YEAR - FIRST_YEAR)
    year = LAST_YEAR - np.floor(age)
    return x.astype(np.float32), year.astype(np.float32)


def regression_rows(data: dict):
    """``data`` is a configuration's ``data`` block.  Returns (x_train,
    y_train, x_test, y_test, mean_year): the data set's own split (the last
    ``round(n_rows * test_frac)`` rows test), targets centred on the
    training split's mean year (float32)."""
    x, year = year_like(n=data["n_rows"], d=data["n_features"],
                        seed=data["geometry_seed"], n_modes=data["n_modes"],
                        noise=data["noise"], n_ridges=data["n_ridges"],
                        shape=data["year_shape"], scale=data["year_scale"])
    n_train = len(x) - int(round(len(x) * data["test_frac"]))
    mean_year = float(np.mean(year[:n_train], dtype=np.float64))
    y = (year - mean_year).astype(np.float32)
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:], mean_year
