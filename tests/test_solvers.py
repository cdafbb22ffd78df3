"""Solver unit tests: every liquidSVM dual reaches its KKT point and the
statistical contract of each loss holds (margin / coverage / expectile).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kernel_fns
from repro.core.solvers import (
    base, expectile as exp_solver, hinge, least_squares as ls, quantile as qs,
)

jax.config.update("jax_enable_x64", False)


def _gram(x, gamma=1.0):
    return kernel_fns.gaussian(jnp.asarray(x, jnp.float32), jnp.asarray(x, jnp.float32),
                               jnp.float32(gamma))


def _gauss_seidel_box_qp(k_mat, y, lo, hi, c0, epochs):
    """Reference for the box QP: exact Gauss-Seidel coordinate descent,
    coordinates 0..n-1 in order, every column at once.  Per coordinate i

        delta = clip(c_i - g_i / K_ii, lo_i, hi_i) - c_i
        c_i  += delta
        g    += K[:, i] (x) delta      (g = K c - y kept up to date)

    the classic liquidSVM/libsvm one-coordinate working-set step."""
    diag = jnp.diag(k_mat)

    def coord(i, state):
        c, g = state
        ci = c[i]
        delta = jnp.clip(ci - g[i] / jnp.maximum(diag[i], 1e-12),
                         lo[i], hi[i]) - ci
        return c.at[i].add(delta), g + k_mat[:, i][:, None] * delta[None, :]

    def epoch(_, state):
        return jax.lax.fori_loop(0, k_mat.shape[0], coord, state)

    return jax.lax.fori_loop(0, epochs, epoch, (c0, k_mat @ c0 - y))[0]


# ---------------------------------------------------------------- box QP core

class TestBoxQP:
    def test_identity_kernel_analytic(self):
        """With K = I the solution is clip(y, lo, hi) exactly."""
        n, p = 40, 7
        rng = np.random.default_rng(1)
        y = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
        lo = jnp.full((n, p), -0.5, jnp.float32)
        hi = jnp.full((n, p), 0.8, jnp.float32)
        res = base.box_qp(jnp.eye(n), y, lo, hi, tol=1e-6, max_iters=5000)
        np.testing.assert_allclose(res.c, np.clip(y, -0.5, 0.8), atol=2e-5)

    def test_kkt_residual_below_tol(self):
        rng = np.random.default_rng(2)
        x = rng.normal(size=(96, 5)).astype(np.float32)
        k = _gram(x)
        y = jnp.asarray(np.sign(rng.normal(size=(96, 4))), jnp.float32)
        lo, hi = jnp.minimum(0.0, y) * 2.0, jnp.maximum(0.0, y) * 2.0
        res = base.box_qp(k, y, lo, hi, tol=1e-4, max_iters=8000)
        assert np.max(np.asarray(res.kkt)) <= 1e-4

    def test_matches_cd_reference_fixed_point(self):
        """FISTA and Gauss-Seidel CD land on the same box-QP optimum."""
        rng = np.random.default_rng(3)
        x = rng.normal(size=(64, 4)).astype(np.float32)
        k = _gram(x) + 1e-3 * jnp.eye(64)
        y = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)
        lo = jnp.full((64, 3), -1.0, jnp.float32)
        hi = jnp.full((64, 3), 1.0, jnp.float32)
        c_fista = base.box_qp(k, y, lo, hi, tol=1e-7, max_iters=20000).c
        c_cd = _gauss_seidel_box_qp(k, y, lo, hi, jnp.zeros((64, 3)), epochs=600)
        np.testing.assert_allclose(c_fista, c_cd, atol=5e-4)

    def test_dual_objective_monotone_in_iterations(self):
        rng = np.random.default_rng(4)
        x = rng.normal(size=(48, 3)).astype(np.float32)
        k = _gram(x)
        y = jnp.asarray(np.sign(rng.normal(size=(48, 1))), jnp.float32)
        lo, hi = jnp.minimum(0.0, y), jnp.maximum(0.0, y)
        objs = []
        for iters in (5, 20, 80, 400):
            c = base.box_qp(k, y, lo, hi, tol=0.0, max_iters=iters).c
            objs.append(float(base.dual_objective(k, y, c)[0]))
        assert objs == sorted(objs) or max(
            objs[i] - objs[i + 1] for i in range(len(objs) - 1)) < 1e-5

    def test_power_iteration_upper_bounds_spectrum(self):
        rng = np.random.default_rng(5)
        a = rng.normal(size=(60, 60)).astype(np.float32)
        k = a @ a.T / 60.0
        l_est = float(base.power_iteration_l(jnp.asarray(k)))
        l_true = float(np.linalg.eigvalsh(k).max())
        assert l_est >= 0.99 * l_true  # 1.05 safety factor in estimator


# ------------------------------------------- box QP loop: blocks and checks

def _box_qp_cond_loop(k_mat, y, lo, hi, c0=None, tol=1e-3, max_iters=2000,
                      l_est=None, check_every=10):
    """The box-QP loop as it was before the check moved to block ends: one
    ``while_loop`` step per iteration, the KKT check in a ``lax.cond``.  The
    reference for ``box_qp``'s iterates, counts and residuals."""
    if k_mat.dtype not in (jnp.bfloat16, jnp.float16):
        k_mat = k_mat.astype(jnp.float32)
    if y.ndim == 1:
        y = y[:, None]
    p = max(y.shape[1], lo.shape[1] if lo.ndim == 2 else 1, hi.shape[1] if hi.ndim == 2 else 1)
    n = k_mat.shape[0]
    y = jnp.broadcast_to(y.astype(jnp.float32), (n, p))
    lo = jnp.broadcast_to(lo.astype(jnp.float32), (n, p))
    hi = jnp.broadcast_to(hi.astype(jnp.float32), (n, p))
    c0 = jnp.zeros((n, p), jnp.float32) if c0 is None else jnp.broadcast_to(c0.astype(jnp.float32), (n, p))
    c0 = base.clip_warm_start(c0, lo, hi)  # warm starts from a larger box are clipped in

    if l_est is None:
        l_est = base.power_iteration_l(k_mat)
    step = 1.0 / l_est

    def grad(c):
        return base._kdot(k_mat, c) - y

    def cond(state):
        c, z, t, it, res = state
        return jnp.logical_and(it < max_iters, jnp.max(res) > tol)

    def body(state):
        c, z, t, it, _ = state
        g = grad(z)
        c_new = jnp.clip(z - step * g, lo, hi)
        # gradient-based adaptive restart (O'Donoghue & Candes)
        restart = jnp.sum(g * (c_new - c)) > 0.0
        t_new = jnp.where(restart, 1.0, 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)))
        beta = jnp.where(restart, 0.0, (t - 1.0) / t_new)
        z_new = c_new + beta * (c_new - c)
        res = jax.lax.cond(
            (it + 1) % check_every == 0,
            lambda: base.kkt_residual(c_new, grad(c_new), lo, hi),
            lambda: jnp.full((p,), jnp.inf, jnp.float32),
        )
        return c_new, z_new, t_new, it + 1, res

    init = (c0, c0, jnp.float32(1.0), jnp.int32(0), jnp.full((p,), jnp.inf, jnp.float32))
    c, _, _, it, _ = jax.lax.while_loop(cond, body, init)
    final_res = base.kkt_residual(c, grad(c), lo, hi)
    return base.BoxQPResult(c=c, kkt=final_res, iters=it, l_est=l_est)


def _hinge_folds(gamma, n=48, folds=5, seed=0):
    """One cell's hinge duals over 4 cost columns, one problem per CV fold:
    K (n, n) shared, y_eff/lo/hi (folds, n, 4) with held-out rows boxed at 0."""
    rng = np.random.default_rng(seed)
    k = _gram(rng.normal(size=(n, 4)), gamma)
    y = np.sign(rng.normal(size=n)).astype(np.float32)
    fold = rng.integers(0, folds, size=n)
    train = np.stack([fold != f for f in range(folds)]).astype(np.float32)[:, :, None]
    edge = y[None, :, None] * np.asarray([1.0, 10.0, 100.0, 1000.0], np.float32) * train
    return (k, jnp.asarray(y[None, :, None] * train),
            jnp.asarray(np.minimum(0.0, edge)), jnp.asarray(np.maximum(0.0, edge)))


def _batched_solve(solve, batching, max_iters, tol):
    """``solve`` on one fold, vmapped over 5 folds, or over 2 slots x 5 folds
    (slot gammas 1.0 and 1.5: at tol 0.03 the first slot's lanes stop at 20
    iterations while the second's run on, through any remainder steps)."""
    def one(k, y, lo, hi):
        return solve(k, y, lo, hi, tol=tol, max_iters=max_iters)
    k, y, lo, hi = _hinge_folds(1.5)
    if batching == "plain":
        return one(k, y[0], lo[0], hi[0])
    folds = jax.vmap(one, in_axes=(None, 0, 0, 0))
    if batching == "folds":
        return folds(k, y, lo, hi)
    slots = [_hinge_folds(1.0), (k, y, lo, hi)]
    return jax.vmap(folds)(*(jnp.stack(a) for a in zip(*slots)))


def _loop_bodies(jaxpr):
    """(body, holds a nested loop) for every while/scan body in jaxpr."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("while", "scan"):
            body = eqn.params["body_jaxpr" if name == "while" else "jaxpr"].jaxpr
            inner = list(_loop_bodies(body))
            yield body, bool(inner)
            yield from inner
        else:
            for sub in _calls(eqn):
                yield from _loop_bodies(sub)


def _calls(eqn):
    """Sub-jaxprs of a non-loop equation (jit calls, cond branches)."""
    for v in eqn.params.values():
        for u in v if isinstance(v, (tuple, list)) else (v,):
            u = getattr(u, "jaxpr", u)
            if hasattr(u, "eqns"):
                yield u


def _dots_feeding(jaxpr):
    """(dot_generals in jaxpr's own body, the most distinct ones feeding
    any select_n); calls are followed, nested loops are not."""
    feeds, n_dots, worst = {}, 0, 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("while", "scan"):
            continue
        src = frozenset().union(*(feeds.get(v, frozenset()) for v in eqn.invars
                                  if not hasattr(v, "val")))
        for sub in _calls(eqn):
            sub_dots, sub_worst = _dots_feeding(sub)
            worst = max(worst, sub_worst)
            src |= {f"{id(eqn)}.{i}" for i in range(sub_dots)}
            n_dots += sub_dots
        if name == "dot_general":
            n_dots += 1
            src |= {str(id(eqn))}
        if name == "select_n":
            worst = max(worst, len(src))
        for v in eqn.outvars:
            feeds[v] = src
    return n_dots, worst


class TestBoxQPBlocks:
    """``box_qp`` checks the KKT residual once per block of ``check_every``
    steps, outside the per-iteration body; the result must be the per-step
    loop's, plain and under vmap, with or without remainder steps."""

    @pytest.mark.parametrize("tol", [0.03, 0.0], ids=["tol_mid", "tol_zero"])
    @pytest.mark.parametrize("max_iters", [25, 40, 300])
    @pytest.mark.parametrize("batching", ["plain", "folds", "slots_folds"])
    def test_matches_cond_loop(self, batching, max_iters, tol):
        got = _batched_solve(base.box_qp, batching, max_iters, tol)
        ref = _batched_solve(_box_qp_cond_loop, batching, max_iters, tol)
        np.testing.assert_array_equal(np.asarray(got.iters), np.asarray(ref.iters))
        np.testing.assert_allclose(np.asarray(got.c), np.asarray(ref.c), rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got.kkt), np.asarray(ref.kkt), rtol=0, atol=1e-6)
        if tol == 0.0:
            assert np.all(np.asarray(got.iters) == max_iters)
        elif batching == "slots_folds":
            # some lanes stop at a block end, others run to the cap
            assert np.unique(np.asarray(got.iters)).size > 1

    def _structure(self, solve, batching):
        k, y, lo, hi = _hinge_folds(1.5)

        def one(k, y, lo, hi):
            return solve(k, y, lo, hi, tol=1e-3, max_iters=1000, l_est=jnp.float32(40.0))
        fn = jax.vmap(one, in_axes=(None, 0, 0, 0))
        args = (k, y, lo, hi)
        if batching == "slots_folds":
            fn, args = jax.vmap(fn), tuple(jnp.stack([a, a]) for a in args)
        bodies = list(_loop_bodies(jax.make_jaxpr(fn)(*args).jaxpr))
        return ([_dots_feeding(b) for b, nested in bodies if not nested],
                [_dots_feeding(b) for b, nested in bodies if nested])

    @pytest.mark.parametrize("batching", ["folds", "slots_folds"])
    def test_vmapped_step_runs_one_gemm(self, batching):
        """Under vmap a ``lax.cond`` becomes a select that runs both branches:
        the per-iteration body must hold the gradient's ``K @ C`` alone, and
        the check's one per block."""
        steps, blocks = self._structure(base.box_qp, batching)
        assert steps and all(n_dots == 1 for n_dots, _ in steps), steps
        assert all(worst <= 1 for _, worst in steps), steps
        assert blocks and all(n_dots == 1 for n_dots, _ in blocks), blocks
        # the guard sees the per-step check of the old loop
        old_steps, _ = self._structure(_box_qp_cond_loop, batching)
        assert [(n, w) for n, w in old_steps] == [(2, 2)], old_steps


# ------------------------------------------------------- warm-start property

class TestWarmStartProperty:
    """A warm start from far OUTSIDE the (lambda, weight) box must land on
    the same optimum as the cold ``c0 = 0`` solve: ``clip_warm_start``
    projects it into the feasible box and every solver's descent from a
    feasible start is monotone.  Exercised through the full
    ``solve_columns_at`` path so each solver's c0 threading is covered."""

    @staticmethod
    def _cell(seed, regression):
        rng = np.random.default_rng(seed)
        n, d = 48, 3
        x = rng.normal(size=(n, d)).astype(np.float32)
        if regression:
            y = (np.sin(x[:, 0]) + 0.1 * rng.normal(size=n)).astype(np.float32)
        else:
            y = np.sign(rng.normal(size=n)).astype(np.float32)
        return x, y

    @pytest.mark.parametrize("solver", ["hinge", "quantile", "expectile", "ls"])
    def test_outside_box_start_matches_cold(self, solver):
        from repro.core import cv
        x, y = self._cell(11, regression=solver != "hinge")
        n = x.shape[0]
        sub = (0.3, 0.7) if solver in ("quantile", "expectile") else (1.0, 2.0)
        cfg = cv.CVConfig(
            solver=solver, n_folds=2, tol=1e-5, max_iters=20000,
            taus=sub if solver in ("quantile", "expectile") else (0.5,),
            weights=sub if solver == "hinge" else (1.0,))
        lams = (0.05, 0.5)
        if solver == "ls":
            sub = (1.0,)
        lam_cols = jnp.asarray(np.repeat(lams, len(sub)), jnp.float32)
        sub_cols = jnp.asarray(np.tile(sub, len(lams)), jnp.float32)
        p = lam_cols.shape[0]
        task_cols = jnp.zeros((p,), jnp.int32)
        args = (jnp.asarray(x), jnp.asarray(y[None, :]),
                jnp.ones((1, n), jnp.float32), jnp.ones((n,), jnp.float32),
                jnp.float32(1.0), lam_cols, sub_cols, task_cols,
                jax.random.PRNGKey(0))

        cold_mean, _, cold_folds = cv.solve_columns_at(*args, cfg)
        # a start orders of magnitude outside any feasible box
        c0_wild = jnp.asarray(50.0 * np.random.default_rng(12).normal(
            size=(n, p)), jnp.float32)
        warm_mean, _, warm_folds = cv.solve_columns_at(*args, cfg, c0=c0_wild)

        scale = max(float(jnp.max(jnp.abs(cold_folds))), 1e-6)
        np.testing.assert_allclose(np.asarray(warm_folds) / scale,
                                   np.asarray(cold_folds) / scale, atol=5e-3)
        np.testing.assert_allclose(np.asarray(warm_mean) / scale,
                                   np.asarray(cold_mean) / scale, atol=5e-3)


# ------------------------------------------------------------------- hinge

class TestHinge:
    def test_separable_margin(self):
        rng = np.random.default_rng(6)
        n = 120
        y = np.sign(rng.normal(size=n)).astype(np.float32)
        x = (rng.normal(size=(n, 2)) + 3.0 * y[:, None]).astype(np.float32)
        k = _gram(x, gamma=3.0)
        lam = jnp.asarray([1e-4], jnp.float32)
        res = hinge.solve_hinge(k, jnp.asarray(y), lam, jnp.float32(n),
                                tol=1e-5, max_iters=10000)
        f = np.asarray(k @ res.c)[:, 0]
        assert np.mean(np.sign(f) == y) == 1.0

    def test_duality_gap_closes(self):
        rng = np.random.default_rng(7)
        n = 100
        y = np.sign(rng.normal(size=n)).astype(np.float32)
        x = (rng.normal(size=(n, 4)) + 1.2 * y[:, None]).astype(np.float32)
        k = _gram(x, gamma=2.0)
        lam = jnp.asarray([1e-3, 1e-2], jnp.float32)
        res = hinge.solve_hinge(k, jnp.asarray(y), lam, jnp.float32(n),
                                tol=1e-6, max_iters=30000)
        gap = np.asarray(hinge.primal_dual_gap(k, jnp.asarray(y), res.c, lam,
                                               jnp.float32(n)))
        assert np.all(gap < 1e-3)

    def test_box_respects_class_weight(self):
        y = jnp.asarray([1.0, -1.0], jnp.float32)
        lam = jnp.asarray([0.1], jnp.float32)
        w = jnp.asarray([2.0, 1.0], jnp.float32)  # +1 class weighted 2x
        lo, hi = hinge.hinge_boxes(y, lam, jnp.float32(2.0), sample_weight=w)
        c = 1.0 / (2.0 * 0.1 * 2.0)
        np.testing.assert_allclose(hi[0, 0], 2.0 * c, rtol=1e-6)
        np.testing.assert_allclose(lo[1, 0], -c, rtol=1e-6)

    def test_masked_samples_are_inert(self):
        """Zero-width box == removing the sample from the dual exactly."""
        rng = np.random.default_rng(8)
        n = 60
        y = np.sign(rng.normal(size=n)).astype(np.float32)
        x = (rng.normal(size=(n, 3)) + 1.5 * y[:, None]).astype(np.float32)
        k_full = _gram(x)
        mask = np.ones(n, np.float32)
        mask[40:] = 0.0
        lam = jnp.asarray([1e-3], jnp.float32)
        res_m = hinge.solve_hinge(k_full, jnp.asarray(y), lam, jnp.float32(40),
                                  train_mask=jnp.asarray(mask), tol=1e-6,
                                  max_iters=20000)
        k_sub = _gram(x[:40])
        res_s = hinge.solve_hinge(k_sub, jnp.asarray(y[:40]), lam,
                                  jnp.float32(40), tol=1e-6, max_iters=20000)
        np.testing.assert_allclose(res_m.c[:40], res_s.c, atol=5e-4)
        np.testing.assert_allclose(res_m.c[40:], 0.0, atol=1e-7)


# ------------------------------------------------------------------ quantile

class TestQuantile:
    @pytest.mark.parametrize("tau", [0.1, 0.5, 0.9])
    def test_pinball_coverage(self, tau):
        rng = np.random.default_rng(9)
        n = 400
        x = rng.uniform(-1, 1, (n, 1)).astype(np.float32)
        y = (np.sin(2 * x[:, 0]) + 0.3 * rng.normal(size=n)).astype(np.float32)
        k = _gram(x, gamma=0.4)
        res = qs.solve_quantile(k, jnp.asarray(y), jnp.asarray([tau], jnp.float32),
                                jnp.asarray([2e-5], jnp.float32), jnp.float32(n),
                                tol=1e-5, max_iters=30000)
        f = np.asarray(k @ res.c)[:, 0]
        cover = float(np.mean(y <= f))
        assert abs(cover - tau) < 0.08, (tau, cover)

    def test_box_is_label_independent(self):
        lo, hi = qs.quantile_boxes(jnp.asarray([0.3]), jnp.asarray([0.1]),
                                   jnp.float32(10.0), n=4)
        c = 1.0 / (2.0 * 0.1 * 10.0)
        np.testing.assert_allclose(lo, np.full((4, 1), (0.3 - 1.0) * c), rtol=1e-6)
        np.testing.assert_allclose(hi, np.full((4, 1), 0.3 * c), rtol=1e-6)


# ----------------------------------------------------------------- LS / KRR

class TestLeastSquares:
    def test_eigh_path_matches_cholesky(self):
        """The CV's lambda path (``solve_columns``, every row training)
        gives each column what ``solve_krr_chol`` gives at its lambda."""
        rng = np.random.default_rng(10)
        x = rng.normal(size=(80, 4)).astype(np.float32)
        y = jnp.asarray(rng.normal(size=80), jnp.float32)
        k = _gram(x)
        lams = jnp.asarray([1e-3, 1e-2, 1e-1], jnp.float32)
        c_path = ls.solve_columns(k, jnp.tile(y[:, None], (1, 3)), lams,
                                  jnp.float32(80), jnp.ones(80), 80)
        for j, lam in enumerate(np.asarray(lams)):
            c_chol = ls.solve_krr_chol(k, y, jnp.float32(lam), jnp.float32(80))
            np.testing.assert_allclose(c_path[:, j], c_chol, atol=2e-3)

    def test_interpolates_at_tiny_lambda(self):
        rng = np.random.default_rng(11)
        x = rng.normal(size=(50, 2)).astype(np.float32)
        y = rng.normal(size=50).astype(np.float32)
        k = _gram(x, gamma=1.5) + 1e-4 * jnp.eye(50)
        c = ls.solve_krr_chol(k, jnp.asarray(y), jnp.float32(1e-9),
                              jnp.float32(50))
        f = np.asarray(k @ c)
        assert np.max(np.abs(f - y)) < 0.15  # f32 conditioning floor

    def test_masked_fold_equals_subproblem(self):
        """``solve_columns`` gathers a fold's training rows (scattered over
        the cell) into its block: the result is the subproblem on those
        rows alone, and exactly 0 on every other row."""
        rng = np.random.default_rng(12)
        x = rng.normal(size=(60, 3)).astype(np.float32)
        y = rng.normal(size=60).astype(np.float32)
        mask = np.ones(60, np.float32); mask[rng.permutation(60)[:15]] = 0.0
        tr = mask > 0
        k = _gram(x)
        c_m = ls.solve_columns(k, jnp.asarray(y)[:, None],
                               jnp.asarray([1e-2], jnp.float32),
                               jnp.float32(45), jnp.asarray(mask), 48)
        c_s = ls.solve_krr_chol(_gram(x[tr]), jnp.asarray(y[tr]),
                                jnp.float32(1e-2), jnp.float32(45))
        np.testing.assert_allclose(np.asarray(c_m)[tr, 0], c_s, atol=1e-3)
        assert np.all(np.asarray(c_m)[~tr] == 0.0)


# ---------------------------------------------------------------- expectile

class TestExpectile:
    def test_tau_half_is_krr(self):
        """tau = 0.5 halves the LS loss => lambda is effectively doubled."""
        rng = np.random.default_rng(13)
        x = rng.normal(size=(70, 3)).astype(np.float32)
        y = rng.normal(size=70).astype(np.float32)
        k = _gram(x)
        c_exp = exp_solver.solve_expectile(
            k, jnp.asarray(y), jnp.asarray([0.5], jnp.float32),
            jnp.asarray([1e-2], jnp.float32), jnp.float32(70))
        c_krr = ls.solve_krr_chol(k, jnp.asarray(y), jnp.float32(2e-2),
                                  jnp.float32(70))
        np.testing.assert_allclose(c_exp[:, 0], c_krr, atol=2e-3)

    def test_expectile_ordering(self):
        """Higher tau => pointwise higher expectile estimate (on average)."""
        rng = np.random.default_rng(14)
        n = 300
        x = rng.uniform(-1, 1, (n, 1)).astype(np.float32)
        y = (0.4 * rng.normal(size=n)).astype(np.float32)
        k = _gram(x, gamma=0.5)
        c = exp_solver.solve_expectile(
            k, jnp.asarray(y), jnp.asarray([0.2, 0.5, 0.8], jnp.float32),
            jnp.asarray([1e-4, 1e-4, 1e-4], jnp.float32), jnp.float32(n))
        f = np.asarray(k @ c)
        assert np.mean(f[:, 0]) < np.mean(f[:, 1]) < np.mean(f[:, 2])

    def test_irls_stationarity(self):
        """At the IRLS fixed point: K c + lam n W^{-1} c - y = 0 on W(c)."""
        rng = np.random.default_rng(15)
        x = rng.normal(size=(40, 2)).astype(np.float32)
        y = rng.normal(size=40).astype(np.float32)
        k = _gram(x)
        tau, lam = 0.7, 1e-2
        c = exp_solver.solve_expectile(
            k, jnp.asarray(y), jnp.asarray([tau], jnp.float32),
            jnp.asarray([lam], jnp.float32), jnp.float32(40), sweeps=40)[:, 0]
        f = np.asarray(k @ c)
        w = np.where(y - f > 0, tau, 1.0 - tau)
        resid = f + lam * 40.0 * np.asarray(c) / w - y
        assert np.max(np.abs(resid)) < 1e-3
