"""The least-squares (lsSVM) solve of the CV against a float64 reference.

What is pinned here and why:
  * every matmul of the ls solve runs at ``HIGHEST``: on a TPU an f32
    matmul at the default precision is one bf16 pass.  The CV's branch
    solves by Cholesky and triangular solves, whose products XLA computes
    at ``HIGHEST``;
  * ``train_cells_waves`` with ``solver="ls"`` over three small cells of the
    benchmark's YearPredictionMSD-shaped rows (d = 90, 5 folds, the 10x10
    grid) selects the same (gamma, lambda), and gives the same validation
    surface and fold-averaged model, as a plain float64 numpy kernel-ridge
    CV;
  * the model is exactly 0 off each fold's training rows at every lambda,
    the smallest included, on the CV's solve;
  * the CV's solve factors a block of ``cv.ls_factor_side(n, folds)``
    rows, not the padded cell: no fold of ``make_fold_masks`` trains on
    more than ``cv.fold_train_bound`` rows (and some fold on exactly that
    many), the compacted solve agrees with the masked solve of the whole
    cell to f32 rounding, and every Cholesky of the ls wave is of that
    side, while the hinge wave does not depend on the ls solve at all.
"""
import importlib.util
import os

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cv as cv_mod
from repro.core import grids, kernel_fns
from repro.core.solvers import least_squares as ls
from repro.distributed import cell_trainer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
K, D, FOLDS = 96, 90, 5
SIZES = (96, 90, 81)                 # ragged real rows, padded to K
# cells wide enough that the factored block is narrower than the cell:
# ls_factor_side(300, 5) = 256
BIG_K, BIG_SIZES = 300, (300, 262, 229)


def _data_reg():
    spec = importlib.util.spec_from_file_location(
        "bench_data_reg", os.path.join(BENCH, "data_reg.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (map, jit) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("solve", ["cv_branch"])
def test_ls_branch_matmuls_run_at_highest(solve):
    n, p = 16, 4
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    k_full = kernel_fns.get_spec("gauss_rbf").fn(x, x, jnp.float32(1.5))
    y_cols = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    train = jnp.asarray(rng.uniform(size=(n, 1)) < 0.7, jnp.float32) \
        * jnp.ones((1, p))
    lam_c = jnp.asarray([1.0, 0.1, 0.01, 0.001], jnp.float32)
    cfg = cv_mod.CVConfig(solver="ls")
    jaxpr = jax.make_jaxpr(
        lambda k, y, t: cv_mod._solve_columns(
            k, y, t, lam_c, jnp.ones(p), jnp.sum(t, axis=0), cfg, None,
            None)[0])(k_full, y_cols, train)
    eqns = list(_eqns(jaxpr.jaxpr))
    names = {e.primitive.name for e in eqns}
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert {"cholesky", "triangular_solve"} <= names
    assert "eigh" not in names
    hi = jax.lax.Precision.HIGHEST
    assert all(e.params["precision"] == (hi, hi) for e in dots), [
        e.params["precision"] for e in dots]


def _cells(seed=0, k=K, sizes=SIZES):
    """Three cells of year_like rows, standardised by their own statistics,
    targets centred on their mean year; padding rows are 0."""
    x_all, year = _data_reg().year_like(n=4000, d=D, seed=seed)
    x_all = (x_all - x_all.mean(0)) / x_all.std(0)
    y_all = year - year.mean()
    x = np.zeros((len(sizes), k, D), np.float32)
    y = np.zeros((len(sizes), 1, k), np.float32)
    m = np.zeros((len(sizes), k), np.float32)
    lo = 0
    for s, size in enumerate(sizes):
        x[s, :size] = x_all[lo:lo + size]
        y[s, 0, :size] = y_all[lo:lo + size]
        m[s, :size] = 1.0
        lo += size
    gam = np.stack([np.asarray(grids.liquid_grid(
        n=size, dim=D, median_dist=float(kernel_fns.median_heuristic(
            jnp.asarray(x[s]), jnp.asarray(m[s]))), grid_choice=0,
        cell_size=2000).gammas, np.float32) for s, size in enumerate(sizes)])
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed), len(sizes)))
    return x, y, m, gam, keys


def _krr_cv64(x, y, m, gammas, lambdas, val):
    """Plain float64 k-fold CV of kernel ridge regression on one cell: one
    dense solve per (fold, gamma, lambda), validation MSE, fold-averaged
    coefficients, and the argmin (first least lambda per gamma, then
    strictly better gammas)."""
    x, y = x.astype(np.float64), y.astype(np.float64)
    sq = (x * x).sum(1)
    d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * x @ x.T, 0.0)
    real = m > 0
    surface = np.zeros((len(gammas), len(lambdas)))
    coefs = np.zeros((len(gammas), len(x), len(lambdas)))
    for g, gamma in enumerate(np.asarray(gammas, np.float64)):
        kg = np.exp(-d2 / gamma ** 2)
        for va in val:
            tr = ~va & real
            k_tt, k_vt = kg[np.ix_(tr, tr)], kg[np.ix_(va, tr)]
            for j, lam in enumerate(np.asarray(lambdas, np.float64)):
                c = np.linalg.solve(k_tt + lam * tr.sum() * np.eye(tr.sum()),
                                    y[tr])
                surface[g, j] += np.mean((y[va] - k_vt @ c) ** 2) / len(val)
                coefs[g, tr, j] += c / len(val)
    best, gi, li = np.inf, 0, 0
    for g in range(len(gammas)):
        j = int(np.argmin(surface[g]))
        if surface[g, j] < best:
            best, gi, li = surface[g, j], g, j
    return surface, coefs, gi, li


def test_train_cells_waves_ls_matches_float64_krr_cv():
    x, y, m, gam, keys = _cells()
    cfg = cv_mod.CVConfig(solver="ls", n_folds=FOLDS, keep_surface=True)
    base = grids.liquid_grid(n=K, dim=D, median_dist=1.0, grid_choice=0,
                             cell_size=2000)
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(base, cfg, 1)

    def stage(lo, hi):
        return x[lo:hi], y[lo:hi], y[lo:hi] * 0 + m[lo:hi, None], m[lo:hi], \
            gam[lo:hi], keys[lo:hi]

    coefs, gamma, lam, _, _, surf = cell_trainer.train_cells_waves(
        stage, len(SIZES), 2, lam_c, sub_c, task_c, cfg, n_lam, n_sub)[:6]
    lambdas = np.asarray(base.lambdas)
    for s in range(len(SIZES)):
        val = np.asarray(cv_mod.make_fold_masks(jnp.asarray(keys[s]),
                                                jnp.asarray(m[s]), FOLDS))
        ref_s, ref_c, gi, li = _krr_cv64(x[s], y[s, 0], m[s], gam[s],
                                         lambdas, val)
        # the same grid point: these cells' two least surface entries are
        # apart by 4e-4 relative or more, the f32 gap near them less
        assert gamma[s, 0, 0] == gam[s, gi] and lam[s, 0, 0] == lambdas[li]
        # validation MSE over all 100 points, lambda_min included: an f32
        # Gram and Cholesky against float64, whose round-off (~eps ||K||
        # ~ 1e-5 against lambda n down to ~1e-4) reads up to 4.4e-5 off
        # on these cells; ~10x room
        np.testing.assert_allclose(surf[s, :, 0, :, 0], ref_s, rtol=5e-4)
        # the fold-averaged model at the selected point, which here lies
        # near lambda_min: the same round-off, 1.6e-5 at most; ~10x room
        c_ref = ref_c[gi, :, li]
        assert np.max(np.abs(coefs[s, :, 0, 0] - c_ref)) \
            <= 2e-4 * np.max(np.abs(c_ref))


@pytest.mark.parametrize("solve", ["solve_columns"])
def test_ls_model_is_zero_off_the_training_rows(solve):
    x, y, m, gam, keys = _cells(seed=1)
    k_full = kernel_fns.get_spec("gauss_rbf").fn(
        jnp.asarray(x[0]), jnp.asarray(x[0]), jnp.float32(gam[0, 0]))
    train = (np.arange(K) % 5 != 0) & (m[0] > 0)
    lams = jnp.asarray(grids.liquid_grid(n=K, dim=D, median_dist=1.0,
                                         grid_choice=0).lambdas)
    t = jnp.asarray(train, jnp.float32)
    c = np.asarray(ls.solve_columns(
        k_full, jnp.asarray(np.repeat(y[0, 0][:, None], len(lams), 1)),
        lams, jnp.float32(train.sum()), t, cv_mod.ls_factor_side(K, 5)))
    assert np.all(c[~train] == 0.0)
    assert np.all(np.isfinite(c))


@pytest.mark.parametrize("n_folds", [2, 5])
@pytest.mark.parametrize("scheme", ["random", "stratified", "blocks"])
def test_fold_training_rows_fit_the_factored_block(scheme, n_folds):
    """Every fold of a cell with 1 to n real rows trains on at most
    ``fold_train_bound(n, n_folds)`` rows, and some fold on exactly that
    many: a bound one smaller would drop a training row."""
    n = 97
    n_real = np.arange(1, n + 1)
    masks = jnp.asarray(np.arange(n)[None, :] < n_real[:, None], jnp.float32)
    labels = np.where(np.random.default_rng(4).uniform(size=n) < 0.3, 1., -1.)
    keys = jax.random.split(jax.random.PRNGKey(3), n)
    val = jax.vmap(lambda key, m: cv_mod.make_fold_masks(
        key, m, n_folds, scheme, jnp.asarray(labels, jnp.float32)))(keys,
                                                                    masks)
    train = np.sum(~np.asarray(val) & (np.asarray(masks) > 0)[:, None, :],
                   axis=-1)                                  # (n_real, folds)
    assert train.max() == cv_mod.fold_train_bound(n, n_folds)


def _masked_full_solve(k_mat, y, lambdas, n_eff, train_mask):
    """The uncompacted CV solve: the whole padded cell masked to the
    fold's training rows (``K m m^T + lambda n I``), factored at full
    side, one Cholesky per lambda."""
    m = train_mask.astype(jnp.float32)
    with jax.named_scope("cv.ls_factor"):
        km = ls._masked(k_mat.astype(jnp.float32), m)
    with jax.named_scope("cv.ls_path"):
        y = (y.astype(jnp.float32) * m[:, None]).T
    n_eff = jnp.broadcast_to(n_eff, lambdas.shape)
    c = jax.lax.map(lambda col: ls.solve_krr_chol(km, *col),
                    (y, lambdas, n_eff))
    return c.T


@pytest.mark.parametrize("scheme", ["random", "stratified", "blocks"])
def test_compacted_solve_matches_the_masked_full_solve(scheme):
    """Each fold of three padded cells, at the smallest, a middle and the
    largest gamma, lambda from 1 down to 1e-5: the compacted solve and the
    masked full-side one solve the same system in f32, so per column they
    differ by no more than f32 rounding through that system: 16 eps (about
    sqrt(n) of the block's rows) times its condition number (float64
    eigenvalues of the fold block) times the column's scale.  Off the
    fold's training rows the model is exactly 0."""
    x, y, m, gam, keys = _cells(seed=2, k=BIG_K, sizes=BIG_SIZES)
    lams = np.geomspace(1.0, 1e-5, 6)
    side = cv_mod.ls_factor_side(BIG_K, FOLDS)
    eps = np.finfo(np.float32).eps
    solves = [jax.jit(jax.vmap(solve, in_axes=(None, None, 0))) for solve in (
        lambda k, yc, t: ls.solve_columns(
            k, yc, jnp.asarray(lams, jnp.float32), jnp.sum(t), t, side),
        lambda k, yc, t: _masked_full_solve(
            k, yc, jnp.asarray(lams, jnp.float32), jnp.sum(t), t))]
    for s in range(len(BIG_SIZES)):
        val = np.asarray(cv_mod.make_fold_masks(
            jnp.asarray(keys[s]), jnp.asarray(m[s]), FOLDS, scheme,
            jnp.asarray(y[s, 0])))
        train = ~val & (m[s] > 0)[None, :]                  # (folds, k)
        y_cols = jnp.asarray(np.repeat(y[s, 0][:, None], len(lams), 1))
        for g in (0, 5, 9):
            k_full = kernel_fns.get_spec("gauss_rbf").fn(
                jnp.asarray(x[s]), jnp.asarray(x[s]), jnp.float32(gam[s, g]))
            new, old = (np.asarray(f(k_full, y_cols,
                                     jnp.asarray(train, jnp.float32)))
                        for f in solves)
            for f, tr in enumerate(train):
                assert np.all(new[f][~tr] == 0.0)
                ev = np.linalg.eigvalsh(
                    np.asarray(k_full, np.float64)[np.ix_(tr, tr)])
                ln = lams * tr.sum()
                cond = (ev[-1] + ln) / (max(ev[0], 0.0) + ln)
                gap = np.max(np.abs(new[f] - old[f]), axis=0)
                tol = 16 * eps * cond * np.max(np.abs(old[f]), axis=0)
                assert np.all(gap <= tol), (s, g, f, gap, tol)


def _lowered(solver, k=300, slots=2):
    cfg = cv_mod.CVConfig(solver=solver, n_folds=FOLDS, max_iters=20,
                          keep_surface=True)
    base = grids.liquid_grid(n=k, dim=3, median_dist=1.0, grid_choice=0,
                             cell_size=2000)
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(base, cfg, 1)
    ones = jnp.ones((slots, 1, k), jnp.float32)
    args = (jnp.zeros((slots, k, 3), jnp.float32), ones, ones,
            jnp.ones((slots, k), jnp.float32),
            jnp.tile(jnp.asarray(base.gammas, jnp.float32)[None], (slots, 1)),
            jax.random.split(jax.random.PRNGKey(0), slots))

    def wave(*a):
        return cell_trainer.train_cells(*a, lam_c, sub_c, task_c, cfg, n_lam,
                                        n_sub)
    return jax.make_jaxpr(wave)(*args), jax.jit(wave).lower(*args).as_text()


def _lowered_with_the_masked_full_solve(solver):
    saved = ls.solve_columns
    ls.solve_columns = lambda k, y, lam, n_eff, t, n_c: _masked_full_solve(
        k, y, lam, n_eff, t)
    try:
        jax.clear_caches()
        return _lowered(solver)[1]
    finally:
        ls.solve_columns = saved
        jax.clear_caches()


@pytest.mark.parametrize("solver", ["ls", "hinge"])
def test_wave_factors_only_the_fold_block(solver):
    """The ls wave's every Cholesky is of side ``ls_factor_side(k,
    folds)`` (k = 300, 5 folds: 256); the hinge wave lowers to the same
    program whichever ls solve the CV holds, while the ls wave does not."""
    jaxpr, text = _lowered(solver)
    chol = [e.invars[0].aval.shape for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name == "cholesky"]
    if solver == "ls":
        side = cv_mod.ls_factor_side(300, FOLDS)
        assert chol and all(s[-2:] == (side, side) for s in chol), chol
        assert _lowered_with_the_masked_full_solve(solver) != text
    else:
        assert not chol
        assert _lowered_with_the_masked_full_solve(solver) == text
