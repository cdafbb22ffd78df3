"""Distance-cache Gram pipeline: kernel parity, symmetry, CV equivalence.

Covers the contract of the gamma-reuse pipeline end to end:

  * ``gram_from_d2`` epilogue == the ``kernel_fns`` oracles on the same D²
    (1e-5 f32; bf16 carries ~8e-3 — one half-precision rounding of values
    in (0, 1], i.e. 2**-7 ulp at the top of the range);
  * the symmetric (upper-triangle + mirror) train-Gram path is EXACTLY
    symmetric, bitwise;
  * ``cv_cell`` with the cached D² selects identical hyper-parameters and
    matches validation losses to <= 1e-5 vs. the per-gamma full Gram, the
    path of a kernel registered without a D² epilogue.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cv as cv_mod
from repro.core import grids, kernel_fns
from repro.core.svm import train_select
from repro.kernels.kernel_matrix.ops import gram_from_d2, kernel_matrix, sq_dists


class TestSqDists:
    @pytest.mark.parametrize("n,m,d", [(128, 128, 8), (100, 37, 5), (130, 257, 33)])
    def test_cross_matches_oracle(self, n, m, d):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        z = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
        got = sq_dists(x, z, force_pallas=True)
        np.testing.assert_allclose(got, kernel_fns.sq_dists(x, z), atol=1e-4)

    @pytest.mark.parametrize("n,d", [(64, 4), (130, 17), (256, 40)])
    def test_symmetric_matches_oracle(self, n, d):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        got = sq_dists(x, x, symmetric=True, force_pallas=True)
        np.testing.assert_allclose(got, kernel_fns.sq_dists(x, x), atol=1e-4)

    @pytest.mark.parametrize("force_pallas", [True, False])
    def test_symmetric_gram_exactly_symmetric(self, force_pallas):
        """Upper-triangle compute + mirror-on-write: K == K.T BITWISE."""
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(150, 9)), jnp.float32)
        d2 = np.asarray(sq_dists(x, x, symmetric=True, force_pallas=force_pallas))
        assert (d2 == d2.T).all()
        k = np.asarray(gram_from_d2(jnp.asarray(d2), jnp.float32(1.7),
                                    force_pallas=force_pallas))
        assert (k == k.T).all()


class TestGramFromD2:
    @pytest.mark.parametrize("kind,oracle", [("gauss_rbf", kernel_fns.gaussian),
                                             ("laplacian", kernel_fns.laplacian)])
    @pytest.mark.parametrize("gamma", [0.4, 1.3, 6.0])
    def test_f32_parity_with_oracle(self, kind, oracle, gamma):
        """Same D² in, epilogue out must match the jnp kernel oracles 1e-5."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(130, 17)), jnp.float32)
        d2 = kernel_fns.sq_dists(x, x)
        got = gram_from_d2(d2, jnp.float32(gamma), kind=kind, force_pallas=True)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, oracle(x, x, jnp.float32(gamma)), atol=1e-5)

    @pytest.mark.parametrize("kind,oracle", [("gauss_rbf", kernel_fns.gaussian),
                                             ("laplacian", kernel_fns.laplacian)])
    def test_bf16_downcast_tolerance(self, kind, oracle):
        """bf16 fused downcast: kernel values live in (0, 1], so one bf16
        rounding is at most 2**-8 relative ~ 8e-3 absolute (documented)."""
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(96, 12)), jnp.float32)
        d2 = kernel_fns.sq_dists(x, x)
        got = gram_from_d2(d2, jnp.float32(1.1), kind=kind, out_dtype="bf16",
                           force_pallas=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   oracle(x, x, jnp.float32(1.1)), atol=8e-3)

    def test_matches_fused_kernel_matrix(self):
        """Split D² + epilogue == the one-shot fused Pallas Gram."""
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(140, 20)), jnp.float32)
        z = jnp.asarray(rng.normal(size=(90, 20)), jnp.float32)
        fused = kernel_matrix(x, z, jnp.float32(2.2), force_pallas=True)
        split = gram_from_d2(sq_dists(x, z, force_pallas=True), jnp.float32(2.2),
                             force_pallas=True)
        np.testing.assert_allclose(split, fused, atol=1e-5)


class TestRegistryFactorization:
    def test_builtins_declare_d2(self):
        assert kernel_fns.factors_through_d2("gauss_rbf")
        assert kernel_fns.factors_through_d2("laplacian")

    def test_custom_kernel_without_epilogue_falls_back(self):
        kernel_fns.register_kernel(
            "_test_poly", lambda x, z, g: (x @ z.T / g) ** 2)
        try:
            assert not kernel_fns.factors_through_d2("_test_poly")
            rng = np.random.default_rng(6)
            x = jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)
            gs = jnp.asarray([1.0, 2.0], jnp.float32)
            ks = kernel_fns.gram_for_gammas(x, x, gs, name="_test_poly")
            np.testing.assert_allclose(
                ks[1], kernel_fns.get_kernel("_test_poly")(x, x, 2.0), atol=1e-5)
        finally:
            kernel_fns.unregister_kernel("_test_poly")

    def test_cached_gram_api(self):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(40, 6)), jnp.float32)
        cg = kernel_fns.CachedGram.build(x, name="gauss_rbf")
        k1 = cg.gram(jnp.float32(1.5))
        np.testing.assert_allclose(k1, kernel_fns.gaussian(x, x, 1.5), atol=1e-5)
        gs = jnp.asarray([0.5, 1.5, 4.0], jnp.float32)
        ks = cg.grams(gs)
        assert ks.shape == (3, 40, 40)
        np.testing.assert_allclose(ks[1], k1, atol=1e-6)
        many = kernel_fns.gram_for_gammas(x, x, gs, symmetric=True)
        np.testing.assert_allclose(many, ks, atol=1e-6)


class TestBF16D2Storage:
    """bf16 D² *storage* (not just bf16 K): the cache leaf itself is 2-byte.

    Error model (Gaussian): d2' = d2 (1 + δ), |δ| <= 2^-8 (bf16 keeps 7
    fraction bits; round-to-nearest half-ulp), so |K' - K| ~= K * (d2/g²)
    * |δ| <= max_u u e^{-u} * 2^-8 = e^{-1} * 2^-8 ~= 1.4e-3 — UNIFORM in
    gamma.  Small gamma makes the epilogue steep (exp(-d2/g²) swings over
    many orders), but the worst-case absolute error stays at the u e^{-u}
    peak; the test pins the analytic bound exactly there.
    """

    # e^{-1} * 2^-8, plus one f32 epilogue rounding of slack
    _GAUSS_BOUND = float(np.exp(-1.0)) * 2.0 ** -8 * 1.05

    @pytest.mark.parametrize("gamma", [0.05, 0.2, 1.0])
    def test_error_bound_small_gamma(self, gamma):
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.normal(size=(160, 10)), jnp.float32)
        cg16 = kernel_fns.CachedGram.build(x, d2_dtype="bf16")
        cg32 = kernel_fns.CachedGram.build(x, d2_dtype="f32")
        assert cg16.d2.dtype == jnp.bfloat16
        assert cg16.nbytes * 2 == cg32.nbytes
        err = np.abs(np.asarray(cg16.gram(jnp.float32(gamma)))
                     - np.asarray(cg32.gram(jnp.float32(gamma))))
        assert err.max() <= self._GAUSS_BOUND, (gamma, err.max())

    def test_cross_gram_fn_threads_dtype(self):
        rng = np.random.default_rng(12)
        x = jnp.asarray(rng.normal(size=(32, 5)), jnp.float32)
        z = jnp.asarray(rng.normal(size=(24, 5)), jnp.float32)
        gram_of = kernel_fns.cross_gram_fn(x, z, d2_dtype="bf16")
        k = gram_of(jnp.float32(0.3))
        ref = kernel_fns.gaussian(x, z, jnp.float32(0.3))
        np.testing.assert_allclose(np.asarray(k), np.asarray(ref),
                                   atol=self._GAUSS_BOUND)

    def test_bad_dtype_raises(self):
        x = jnp.zeros((8, 2), jnp.float32)
        with pytest.raises(ValueError):
            kernel_fns.CachedGram.build(x, d2_dtype="fp8")


@contextlib.contextmanager
def _without_epilogue(kernel: str):
    """``kernel``'s function registered under a second name with no D²
    epilogue, so ``cv_cell`` evaluates it in full once per gamma."""
    name = f"_{kernel}_full_gram"
    kernel_fns.register_kernel(name, kernel_fns.get_kernel(kernel))
    try:
        yield name
    finally:
        kernel_fns.unregister_kernel(name)


class TestCVEquivalence:
    @pytest.mark.parametrize("solver,kernel", [("hinge", "gauss_rbf"),
                                               ("ls", "gauss_rbf"),
                                               ("hinge", "laplacian")])
    def test_cached_selects_same_hyperparams(self, solver, kernel):
        """The cached D² must select the same (gamma, lambda) and match the
        validation loss to <= 1e-5 vs. the per-gamma full Gram."""
        rng = np.random.default_rng(8)
        n = 120
        y = np.sign(rng.normal(size=n)).astype(np.float32)
        x = (rng.normal(size=(n, 3)) + 1.2 * y[:, None]).astype(np.float32)
        g = grids.GridSpec(gammas=jnp.asarray([4.0, 2.0, 1.0, 0.5], jnp.float32),
                           lambdas=jnp.asarray([1.0, 0.1, 0.01], jnp.float32))
        cfg = cv_mod.CVConfig(solver=solver, kernel=kernel, n_folds=3,
                              max_iters=200)
        m_cached = train_select(x, y, grid=g, cfg=cfg, seed=3)
        with _without_epilogue(kernel) as full:
            m_base = train_select(x, y, grid=g, seed=3,
                                  cfg=dataclasses.replace(cfg, kernel=full))
        assert float(m_cached.gamma[0, 0]) == float(m_base.gamma[0, 0])
        assert float(m_cached.lam[0, 0]) == float(m_base.lam[0, 0])
        np.testing.assert_allclose(m_cached.val_loss, m_base.val_loss, atol=1e-5)

    def test_full_cv_surface_close(self):
        rng = np.random.default_rng(9)
        n = 100
        y = np.sign(rng.normal(size=n)).astype(np.float32)
        x = (rng.normal(size=(n, 4)) + y[:, None]).astype(np.float32)
        g = grids.GridSpec(gammas=jnp.asarray([3.0, 1.0, 0.3], jnp.float32),
                           lambdas=jnp.asarray([0.5, 0.05], jnp.float32))
        cfg = cv_mod.CVConfig(n_folds=3, max_iters=150)
        lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(g, cfg, 1)
        args = (x, y[None, :], jnp.ones((1, n), jnp.float32),
                jnp.ones((n,), jnp.float32), g.gammas, lam_c, sub_c, task_c,
                jnp.zeros(2, jnp.uint32))
        sel_c = cv_mod.cv_cell(*args, cfg, n_lam=n_lam, n_sub=n_sub)
        with _without_epilogue(cfg.kernel) as full:
            sel_b = cv_mod.cv_cell(*args, dataclasses.replace(cfg, kernel=full),
                                   n_lam=n_lam, n_sub=n_sub)
        np.testing.assert_allclose(sel_c.val_grid, sel_b.val_grid, atol=1e-5)
