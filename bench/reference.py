"""Plain references that decide ``correct``.  They import nothing of the
program and take nothing it made: only the rows, labels, grids, fold seeds,
centres and coefficients that the benchmark itself drew.

* :func:`cv_cell` — liquidSVM's k-fold CV of one working set over a
  (gamma, lambda) grid with the hinge loss, written out step by step: the
  Gram from squared distances, one FISTA box-QP per fold over all lambda
  columns at once (warm-started from the previous gamma, adaptive restart,
  KKT stop every 10 iterations, step 1/L from power iteration), 0-1
  validation loss, argmin selection.  It follows the algorithm as the
  configuration states it, on the padded working set the call receives
  (padding rows have a zero-width box, so they never enter a model, but
  their Gram rows enter the power iteration, as in the configured solver).
* :func:`decisions` — nearest-centre routing and ``sum_i c_i k(x, sv_i)``.

Both compute in f32 with every matmul at ``PRECISION`` (``HIGHEST``).  The
control (``bench/control.py``) loads its own copy of this module with a lower
``PRECISION`` and puts it in the program's place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = jax.lax.Precision.HIGHEST


def median_dist(x, mask, max_points: int = 512) -> float:
    """Median pairwise distance over a strided subsample of at most
    ``max_points`` rows of the padded set (real pairs, off the diagonal),
    in float64."""
    stride = max(1, x.shape[0] // max_points)
    xs = np.asarray(x, np.float64)[::stride]
    ms = np.asarray(mask)[::stride] > 0
    sq = (xs * xs).sum(-1)
    d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * xs @ xs.T, 0.0)
    ok = ms[:, None] & ms[None, :] & ~np.eye(len(xs), dtype=bool)
    return float(np.sqrt(max(np.median(d2[ok]), 1e-12)))


def liquid_grid(n: int, dim: int, median: float, cell_size: int,
                n_gamma: int = 10, n_lambda: int = 10):
    """liquidSVM's geometric grid: gammas from 5x the median distance down
    to the nearest-neighbour spacing of a fold, lambdas from 1 down to
    1 / (4 n_fold^2).  Returns float64 (gammas, lambdas)."""
    n_fold = max(int(n * 0.8), 2)
    k = min(cell_size, n_fold)
    g_max = 5.0 * median
    g_min = median * (max(k, 2) / n_fold) ** (1.0 / dim) / n_fold ** (1.0 / dim)
    g_min = min(g_min, g_max / 8.0)
    gammas = g_max * (g_min / g_max) ** np.linspace(0.0, 1.0, n_gamma)
    lambdas = (1.0 / (4.0 * n_fold ** 2)) ** np.linspace(0.0, 1.0, n_lambda)
    return gammas, lambdas


def fold_masks(key, mask, n_folds: int):
    """(n_folds, n) validation membership: a uniform draw per row from the
    cell's fold key, rows ranked by it, rank mod n_folds (padding: none)."""
    u = jax.random.uniform(key, mask.shape)
    u = jnp.where(mask > 0, u, jnp.inf)
    rank = jnp.argsort(jnp.argsort(u))
    fold_of = jnp.where(mask > 0, rank % n_folds, -1)
    return fold_of[None, :] == jnp.arange(n_folds)[:, None]


def sq_dists(a, b):
    aa = jnp.sum(a * a, axis=-1)
    bb = jnp.sum(b * b, axis=-1)
    ab = jnp.matmul(a, b.T, precision=PRECISION)
    return jnp.maximum(aa[:, None] + bb[None, :] - 2.0 * ab, 0.0)


_d2 = jax.jit(lambda x: sq_dists(x, x))


@functools.partial(jax.jit, static_argnames=("n_folds", "tol", "max_iters"))
def _cv_gamma(d2, y, mask, gamma, lambdas, val, c_warm, n_folds, tol,
              max_iters):
    """One gamma of the CV: every fold's box-QP over all lambda columns."""
    k = jnp.exp(-d2 / jnp.maximum(gamma * gamma, 1e-12))

    def kdot(c):
        return jnp.matmul(k, c, precision=PRECISION)

    v = jax.random.normal(jax.random.PRNGKey(0), (k.shape[0],), jnp.float32)
    for _ in range(32):
        w = kdot(v[:, None])[:, 0]
        v = w / jnp.maximum(jnp.linalg.norm(w), 1e-30)
    lip = jnp.maximum(jnp.dot(v, kdot(v[:, None])[:, 0],
                              precision=PRECISION), 1e-12) * 1.05
    step = 1.0 / lip

    def one_fold(va, c0):
        tr = ((~va) & (mask > 0)).astype(jnp.float32)                 # (n,)
        n_eff = jnp.maximum(jnp.sum(tr), 1.0)
        cost = 1.0 / (2.0 * lambdas * n_eff)                           # (P,)
        edge = (y * tr)[:, None] * cost[None, :]
        lo, hi = jnp.minimum(0.0, edge), jnp.maximum(0.0, edge)
        ye = (y * tr)[:, None]
        width = jnp.maximum(jnp.max(hi - lo, axis=0), 1e-30)

        def kkt(c):
            r = c - jnp.clip(c - (kdot(c) - ye), lo, hi)
            return jnp.max(jnp.abs(r), axis=0) / width

        def body(s):
            c, z, t, it, _ = s
            g = kdot(z) - ye
            c1 = jnp.clip(z - step * g, lo, hi)
            restart = jnp.sum(g * (c1 - c)) > 0.0
            t1 = jnp.where(restart, 1.0, 0.5 * (1.0 + jnp.sqrt(1.0 + 4 * t * t)))
            beta = jnp.where(restart, 0.0, (t - 1.0) / t1)
            res = jax.lax.cond((it + 1) % 10 == 0, lambda: kkt(c1),
                               lambda: jnp.full(lambdas.shape, jnp.inf))
            return c1, c1 + beta * (c1 - c), t1, it + 1, res

        c0 = jnp.clip(c0, lo, hi)
        s = (c0, c0, jnp.float32(1.0), jnp.int32(0),
             jnp.full(lambdas.shape, jnp.inf))
        c = jax.lax.while_loop(
            lambda s: (s[3] < max_iters) & (jnp.max(s[4]) > tol), body, s)[0]
        f = kdot(c)
        vm = (va & (mask > 0)).astype(jnp.float32)[:, None]
        wrong = ((f * y[:, None]) <= 0.0).astype(jnp.float32)
        return jnp.sum(wrong * vm, axis=0) / jnp.maximum(jnp.sum(vm), 1.0), c

    loss, coefs = jax.vmap(one_fold)(val, c_warm)        # (F, P), (F, n, P)
    return jnp.mean(loss, axis=0), coefs


def cv_cell(x, y, mask, gammas, lambdas, fold_key, *, n_folds: int = 5,
            tol: float = 1e-3, max_iters: int = 1000) -> dict:
    """One working set's CV.  ``x`` (n, d) f32, ``y`` (n,) in {-1, 0, +1}
    (0 on padding), ``mask`` (n,), ``gammas`` (G,), ``lambdas`` (L,).

    Returns host arrays: ``surface`` (G, L) mean 0-1 validation loss,
    ``coefs`` (G, n, L) fold-averaged models at every grid point, and the
    argmin's ``g_idx``, ``l_idx`` (first minimum per gamma, then strictly
    better gammas only)."""
    x, y, mask = (jnp.asarray(a, jnp.float32) for a in (x, y, mask))
    lambdas = jnp.asarray(lambdas, jnp.float32)
    val = fold_masks(jnp.asarray(fold_key), mask, n_folds)
    d2 = _d2(x)
    warm = jnp.zeros((n_folds, x.shape[0], lambdas.shape[0]), jnp.float32)
    surface, coefs = [], []
    for g in np.asarray(gammas, np.float32):
        loss, warm = _cv_gamma(d2, y, mask, jnp.float32(g), lambdas, val,
                               warm, n_folds, float(tol), int(max_iters))
        surface.append(np.asarray(loss))
        coefs.append(np.asarray(jnp.mean(warm, axis=0)))
    surface = np.stack(surface)
    best, g_idx, l_idx = np.inf, 0, 0
    for g in range(surface.shape[0]):
        l_star = int(np.argmin(surface[g]))
        if surface[g, l_star] < best:
            best, g_idx, l_idx = surface[g, l_star], g, l_star
    return {"surface": surface, "coefs": np.stack(coefs),
            "g_idx": g_idx, "l_idx": l_idx}


@jax.jit
def _route(xs, centers):
    d2 = sq_dists(xs, centers)
    top2 = jax.lax.top_k(-d2, 2)
    return top2[1], -top2[0], jnp.sum(xs * xs, -1)


@jax.jit
def _cell_decisions(xr, sv, coef, gamma):
    """xr (C, m, d) rows routed to cell c; sv (C, k, d); coef (C, k);
    gamma (C,) -> decisions and their scale sum_i |c_i| K_i, (C, m)."""
    def one(xc, s, c, g):
        k = jnp.exp(-sq_dists(xc, s) / jnp.maximum(g * g, 1e-12))
        return (jnp.matmul(k, c, precision=PRECISION),
                jnp.matmul(k, jnp.abs(c), precision=PRECISION))
    return jax.vmap(one)(xr, sv, coef, gamma)


def decisions(rows, mean, std, centers, sv, coef, gamma, *,
              tie_ulps: float = 64.0) -> dict:
    """Serve ``rows`` (m, d) raw features from the bank the benchmark drew:
    scale, route to the nearest centre, sum the cell's kernel expansion.

    A row whose two nearest centres lie within ``tie_ulps`` f32 ulps of
    |x|^2 + |centre|^2 is a tie: either cell is a right answer, and both
    decisions are returned (``alt``).  Returns host arrays ``cell``,
    ``dec``, ``scale``, ``alt_cell``, ``alt_dec`` (alt = -1 / nan when no
    tie)."""
    xs = ((np.asarray(rows, np.float32) - mean) / std).astype(np.float32)
    idx, d2, xx = (np.asarray(a) for a in _route(
        jnp.asarray(xs), jnp.asarray(centers, jnp.float32)))
    cc = np.sum(np.asarray(centers, np.float64) ** 2, -1)
    eps = float(np.finfo(np.float32).eps)
    gap = d2[:, 1] - d2[:, 0]
    tie = gap <= tie_ulps * eps * (xx + cc[idx[:, 0]])
    cell = idx[:, 0]
    dec, scale = _by_cell(xs, cell, sv, coef, gamma)
    alt = np.where(tie, idx[:, 1], -1)
    alt_dec = np.full(len(xs), np.nan, np.float32)
    if tie.any():
        alt_dec[tie], _ = _by_cell(xs[tie], alt[tie], sv, coef, gamma)
    return {"cell": cell, "dec": dec, "scale": scale, "alt_cell": alt,
            "alt_dec": alt_dec}


def _by_cell(xs, cell, sv, coef, gamma):
    """Group rows by cell, pad to one (C, m, d) block, one vmapped call."""
    cells, inv, counts = np.unique(cell, return_inverse=True,
                                   return_counts=True)
    m = int(-(-counts.max() // 8) * 8)
    pos = np.zeros(len(cell), np.int64)
    seen = np.zeros(len(cells), np.int64)
    for i, c in enumerate(inv):
        pos[i], seen[c] = seen[c], seen[c] + 1
    xr = np.zeros((len(cells), m, xs.shape[1]), np.float32)
    xr[inv, pos] = xs
    dec, scale = _cell_decisions(
        jnp.asarray(xr), jnp.asarray(sv[cells], jnp.float32),
        jnp.asarray(coef[cells], jnp.float32),
        jnp.asarray(gamma[cells], jnp.float32))
    return np.asarray(dec)[inv, pos], np.asarray(scale)[inv, pos]
