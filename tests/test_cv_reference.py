"""``cv_cell`` against an independent float64 k-fold CV, every solver.

The reference below is plain numpy in float64 and imports nothing of the
program but ``make_fold_masks`` (so both sides use the same folds).  Per
(gamma, fold, column) it builds the fold's training Gram from the raw rows
and solves the fold's problem to a tight tolerance:

  * hinge and quantile: the box QP ``min 0.5 c'Kc - c'y, lo <= c <= hi`` by
    accelerated projected gradient, run until its projected-gradient
    residual is at float64 round-off;
  * ls: ``(K + lambda n I) c = y`` by ``np.linalg.solve``;
  * expectile: IRLS, ``(K + lambda n W^-1) c = y`` with ``W`` the residual
    signs' weights, until the weights stop changing (then c is exact).

It scores each fold's validation rows with the loss the program selects on
(0-1 for hinge, pinball, expectile, squared), averages over folds, and
picks the first least (gamma, lambda) in grid order.

Each case is a cell of 40 real rows, 3 folds and a 3 x 3 grid, with and
without 8 padding rows scattered through the cell, with both built-in
kernels.  The program's validation surface must match the reference's
within a tolerance; where the reference's best point beats every other by
more than the two points' tolerances, the program must select it; and its
fold models must be exactly 0 on each fold's padding and validation rows.

Last, ``solve_columns_at`` (the select phase's re-solve, which builds its
Gram with the kernel's full function where the CV uses the cached D²) must
reproduce the CV's fold models at the selected columns from the same fold
key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cv as cv_mod
from repro.core.grids import GridSpec

N_REAL, D, FOLDS, PAD = 40, 3, 3, 8
GAMMAS = (4.0, 1.5, 0.6)
LAMBDAS = (1e-1, 1e-2, 1e-3)
SUBS = {"hinge": (1.0, 2.0), "ls": (1.0,), "quantile": (0.25, 0.75),
        "expectile": (0.25, 0.75)}
SOLVERS = ("hinge", "quantile", "expectile", "ls")
KERNELS = ("gauss_rbf", "laplacian")


def _cell(solver, pad, seed=0):
    """A cell of N_REAL rows and ``pad`` padding rows at random places;
    padding rows carry features but no label (the program's contract)."""
    rng = np.random.default_rng(seed)
    n = N_REAL + pad
    x = rng.normal(size=(n, D)).astype(np.float32)
    mask = np.ones(n, np.float32)
    mask[rng.permutation(n)[:pad]] = 0.0
    if solver == "hinge":
        y = np.where(x[:, 0] + 0.5 * x[:, 1] + 0.6 * rng.normal(size=n) > 0,
                     1.0, -1.0)
    else:
        y = np.sin(2.0 * x[:, 0]) + 0.5 * x[:, 1] + 0.3 * rng.normal(size=n)
    return x, (y * mask).astype(np.float32), mask


def _cfg(solver, kernel):
    sub = SUBS[solver]
    return cv_mod.CVConfig(
        solver=solver, kernel=kernel, n_folds=FOLDS, tol=1e-6,
        max_iters=20000, keep_surface=True,
        taus=sub if solver in ("quantile", "expectile") else (0.5,),
        weights=sub if solver == "hinge" else (1.0,))


# ------------------------------------------------------------ the reference

def _gram64(xa, xb, gamma, kernel):
    d2 = ((xa[:, None, :] - xb[None, :, :]) ** 2).sum(-1)
    if kernel == "gauss_rbf":
        return np.exp(-d2 / gamma ** 2)
    return np.exp(-np.sqrt(d2) / gamma)


def _box_qp64(k, y, lo, hi):
    """min 0.5 c'Kc - c'y per column of y/lo/hi (n, P), lo <= c <= hi:
    FISTA with gradient restart at step 1/lambda_max(K), until the
    projected-gradient residual stops falling (float64 round-off)."""
    step = 1.0 / np.linalg.eigvalsh(k)[-1]
    c = z = np.zeros_like(y)
    t = 1.0
    for it in range(200000):
        g = k @ z - y
        c_new = np.clip(z - step * g, lo, hi)
        if np.sum(g * (c_new - c)) > 0.0:
            t_new, beta = 1.0, 0.0
        else:
            t_new = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * t * t))
            beta = (t - 1.0) / t_new
        z = c_new + beta * (c_new - c)
        c, t = c_new, t_new
        if it % 50 == 0:
            res = np.max(np.abs(c - np.clip(c - (k @ c - y), lo, hi)))
            if res <= 1e-11 * max(1.0, np.max(np.abs(hi - lo))):
                break
    return c


def _expectile64(k, y, tau, lam_n):
    c = np.zeros_like(y)
    w = None
    for _ in range(200):
        w_new = np.where(y - k @ c > 0, tau, 1.0 - tau)
        if w is not None and np.array_equal(w, w_new):
            break
        w = w_new
        c = np.linalg.solve(k + np.diag(lam_n / w), y)
    return c


def _fold_solve64(solver, k, y, lam, sub):
    """One fold's training problem at one column (n_tr rows)."""
    n = len(y)
    if solver == "ls":
        return np.linalg.solve(k + lam * n * np.eye(n), y)
    if solver == "expectile":
        return _expectile64(k, y, sub, lam * n)
    cost = 1.0 / (2.0 * lam * n)
    if solver == "hinge":
        edge = y * cost * np.where(y > 0, sub, 1.0)
        lo, hi = np.minimum(0.0, edge), np.maximum(0.0, edge)
    else:
        lo, hi = np.full(n, cost * (sub - 1.0)), np.full(n, cost * sub)
    return _box_qp64(k, y[:, None], lo[:, None], hi[:, None])[:, 0]


def _loss64(solver, y, f, sub):
    r = y - f
    if solver == "hinge":
        return (f * y <= 0.0).astype(np.float64)
    if solver == "ls":
        return r * r
    if solver == "quantile":
        return np.where(r >= 0, sub * r, (sub - 1.0) * r)
    return np.where(r >= 0, sub, 1.0 - sub) * r * r


def _cv64(solver, kernel, x, y, val, real):
    """Validation surface (gamma, lambda, sub), fold models (gamma, lambda,
    sub, fold, n), and for hinge the surface's slack: per point, how much
    the 0-1 loss moves if every validation row flips whose reference
    decision lies within 1e-3 of the fold model's largest |decision| on
    the cell's real rows."""
    x, y = x.astype(np.float64), y.astype(np.float64)
    subs, n = SUBS[solver], len(y)
    shape = (len(GAMMAS), len(LAMBDAS), len(subs))
    surf, slack = np.zeros(shape), np.zeros(shape)
    models = np.zeros(shape + (FOLDS, n))
    for g, gamma in enumerate(GAMMAS):
        for f, va in enumerate(val):
            tr = ~va & real
            k_rt = _gram64(x[real], x[tr], gamma, kernel)
            k_tt = k_rt[tr[real]]
            for li, lam in enumerate(LAMBDAS):
                for s, sub in enumerate(subs):
                    c = _fold_solve64(solver, k_tt, y[tr], lam, sub)
                    f_real = k_rt @ c
                    f_va = f_real[va[real]]
                    surf[g, li, s] += np.mean(
                        _loss64(solver, y[va], f_va, sub)) / FOLDS
                    if solver == "hinge":
                        slack[g, li, s] += np.mean(
                            np.abs(f_va) <= 1e-3 * np.max(np.abs(f_real))
                        ) / FOLDS
                    models[g, li, s, f, tr] = c
    return surf, slack, models


# ---------------------------------------------------------------- the runs

@functools.lru_cache(maxsize=None)
def _run(solver, kernel, pad):
    x, y, mask = _cell(solver, pad)
    cfg = _cfg(solver, kernel)
    grid = GridSpec(gammas=jnp.asarray(GAMMAS, jnp.float32),
                    lambdas=jnp.asarray(LAMBDAS, jnp.float32))
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(grid, cfg, 1)
    key = jax.random.PRNGKey(7)
    sel = cv_mod.cv_cell(jnp.asarray(x), jnp.asarray(y)[None],
                         jnp.asarray(mask)[None], jnp.asarray(mask),
                         grid.gammas, lam_c, sub_c, task_c, key, cfg,
                         n_lam=n_lam, n_sub=n_sub)
    val = np.asarray(cv_mod.make_fold_masks(
        key, jnp.asarray(mask), FOLDS, cfg.fold_scheme,
        jnp.asarray(y) if solver == "hinge" else None))
    return x, y, mask, key, cfg, sel, val


def _decisions(x, real, gamma, kernel, c):
    """Fold models c (fold, n) evaluated in float64 on the real rows."""
    x = x.astype(np.float64)
    return np.asarray(c, np.float64) @ _gram64(x, x[real], gamma, kernel)


def _surface_tol(solver, surf, slack):
    """Per grid point.  The program's decisions are within ~4e-5 of the
    largest |decision| of the reference's (f32 Gram and FISTA at tol 1e-6,
    where the reference solves in float64): a 0-1 loss moves only on rows
    within the hinge slack, and the continuous losses by ~1.3e-5 relative
    (quantile, the widest), 15 x under 2e-4."""
    if solver == "hinge":
        return slack + 1e-6
    return 2e-4 * np.abs(surf) + 1e-6


@pytest.mark.parametrize("pad", [0, PAD], ids=["nopad", "pad8"])
@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("solver", SOLVERS)
def test_cv_cell_matches_float64_cv(solver, kernel, pad):
    x, y, mask, key, cfg, sel, val = _run(solver, kernel, pad)
    real = mask > 0
    surf, slack, models = _cv64(solver, kernel, x, y, val, real)
    tol = _surface_tol(solver, surf, slack)
    got = np.asarray(sel.val_grid)[:, 0]                  # (gamma, lam, sub)
    assert np.all(np.abs(got - surf) <= tol), np.max(np.abs(got - surf) - tol)

    coefs = np.asarray(sel.coefs)[:, :, 0]                # (fold, n, sub)
    for f, va in enumerate(val):
        off = ~(~va & real)
        assert np.all(coefs[f][off] == 0.0), (f, np.abs(coefs[f][off]).max())

    for s in range(len(SUBS[solver])):
        # the first least point of its own surface, in grid order (the 0-1
        # loss ties often)
        g, li = divmod(int(np.argmin(got[..., s])), len(LAMBDAS))
        assert sel.gamma[0, s] == np.float32(GAMMAS[g])
        assert sel.lam[0, s] == np.float32(LAMBDAS[li])
        # the reference's best, wherever no other point comes near it
        flat, t = surf[..., s].ravel(), tol[..., s].ravel()
        best = int(np.argmin(flat))
        others = np.delete(np.arange(flat.size), best)
        if np.all(flat[others] - t[others] > flat[best] + t[best]):
            assert (g, li) == divmod(best, len(LAMBDAS))
        # at that choice its fold models make the reference's decisions on
        # the cell's real rows (~4e-5 off at most; 12 x room)
        f_ref, f_got = (_decisions(x, real, GAMMAS[g], kernel, c)
                        for c in (models[g, li, s], coefs[:, :, s]))
        assert np.max(np.abs(f_got - f_ref)) <= 5e-4 * np.max(np.abs(f_ref))


@pytest.mark.parametrize("solver", SOLVERS)
def test_select_resolve_matches_the_cv(solver):
    """The re-solve at the CV's selected columns, from a cold start, gives
    the CV's fold models (reached through the gamma scan's warm starts):
    the same zeros, the same decisions on the real rows."""
    x, y, mask, key, cfg, sel, val = _run(solver, "gauss_rbf", PAD)
    real = mask > 0
    coefs = np.asarray(sel.coefs)[:, :, 0]                # (fold, n, sub)
    for s, sub in enumerate(SUBS[solver]):
        _, _, fold_c = cv_mod.solve_columns_at(
            jnp.asarray(x), jnp.asarray(y)[None], jnp.asarray(mask)[None],
            jnp.asarray(mask), sel.gamma[0, s], sel.lam[0, s][None],
            jnp.asarray([sub], jnp.float32), jnp.zeros(1, jnp.int32), key,
            cfg)
        fold_c = np.asarray(fold_c)[:, :, 0]
        for f, va in enumerate(val):
            assert np.all(fold_c[f][~(~va & real)] == 0.0)
        gamma = float(sel.gamma[0, s])
        f_cv, f_re = (_decisions(x, real, gamma, "gauss_rbf", c)
                      for c in (coefs[:, :, s], fold_c))
        assert np.max(np.abs(f_re - f_cv)) <= 5e-4 * np.max(np.abs(f_cv)), (
            np.max(np.abs(f_re - f_cv)) / np.max(np.abs(f_cv)))
