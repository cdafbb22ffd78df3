"""Per-kernel Pallas-vs-oracle validation (interpret=True executes the kernel
body on CPU).  Shapes sweep non-multiples of the 128 tile to exercise the
padding paths; dtypes sweep f32/bf16 inputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.kernel_matrix import ref as km_ref
from repro.kernels.kernel_matrix.ops import kernel_matrix
from repro.kernels.svm_predict import ref as sp_ref
from repro.kernels.svm_predict.ops import svm_predict


# ------------------------------------------------------------- kernel_matrix

class TestKernelMatrix:
    @pytest.mark.parametrize("n,m,d", [(128, 128, 8), (256, 128, 64),
                                       (100, 37, 5), (130, 257, 200)])
    @pytest.mark.parametrize("kind", ["gauss_rbf", "laplacian"])
    def test_matches_ref(self, n, m, d, kind):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        z = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
        got = kernel_matrix(x, z, jnp.float32(1.3), kind=kind, force_pallas=True)
        want = km_ref.kernel_matrix_ref(x, z, jnp.float32(1.3), kind)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_bf16_inputs_upcast(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(64, 16)), jnp.bfloat16)
        got = kernel_matrix(x, x, jnp.float32(2.0), force_pallas=True)
        want = km_ref.kernel_matrix_ref(x, x, jnp.float32(2.0), "gauss_rbf")
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=1e-2)

    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(2, 160), d=st.integers(1, 40),
           gamma=st.floats(0.2, 8.0))
    def test_property_gram_valid(self, n, d, gamma):
        """Gram of the Gaussian kernel: symmetric, unit diagonal, in (0, 1]."""
        rng = np.random.default_rng(42)
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        k = np.asarray(kernel_matrix(x, x, jnp.float32(gamma), force_pallas=True))
        np.testing.assert_allclose(k, k.T, atol=1e-5)
        # the MXU-friendly ||u||^2+||v||^2-2uv decomposition loses ~1e-4 of
        # d^2 to f32 cancellation; at small gamma that shows up on the diag
        # as exp(-eps/gamma^2) != 1 — inherent to the paper's own GPU trick
        np.testing.assert_allclose(np.diag(k), 1.0, atol=5e-3)
        assert (k >= 0).all() and (k <= 1.0 + 1e-4).all()  # exp may underflow to 0


# ----------------------------------------------------------------- svm_predict

class TestSVMPredict:
    @pytest.mark.parametrize("nt,ns,d,p", [(128, 128, 8, 1), (100, 250, 17, 12),
                                           (257, 64, 4, 3)])
    @pytest.mark.parametrize("kind", ["gauss_rbf", "laplacian"])
    def test_matches_ref(self, nt, ns, d, p, kind):
        rng = np.random.default_rng(5)
        xt = jnp.asarray(rng.normal(size=(nt, d)), jnp.float32)
        sv = jnp.asarray(rng.normal(size=(ns, d)), jnp.float32)
        cf = jnp.asarray(rng.normal(size=(ns, p)), jnp.float32)
        got = svm_predict(xt, sv, cf, jnp.float32(1.1), kind=kind, force_pallas=True)
        want = sp_ref.svm_predict_ref(xt, sv, cf, jnp.float32(1.1), kind)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_1d_coef_roundtrip(self):
        rng = np.random.default_rng(6)
        xt = jnp.asarray(rng.normal(size=(50, 3)), jnp.float32)
        sv = jnp.asarray(rng.normal(size=(70, 3)), jnp.float32)
        cf = jnp.asarray(rng.normal(size=70), jnp.float32)
        got = svm_predict(xt, sv, cf, jnp.float32(0.9), force_pallas=True)
        assert got.shape == (50,)

    @settings(max_examples=10, deadline=None)
    @given(nt=st.integers(1, 140), ns=st.integers(1, 140), d=st.integers(1, 24))
    def test_property_matches_dense_path(self, nt, ns, d):
        """Fused predict == materialized Gram @ coefs for any shape."""
        rng = np.random.default_rng(7)
        xt = jnp.asarray(rng.normal(size=(nt, d)), jnp.float32)
        sv = jnp.asarray(rng.normal(size=(ns, d)), jnp.float32)
        cf = jnp.asarray(rng.normal(size=(ns, 2)), jnp.float32)
        got = svm_predict(xt, sv, cf, jnp.float32(1.5), force_pallas=True)
        k = km_ref.kernel_matrix_ref(xt, sv, jnp.float32(1.5), "gauss_rbf")
        np.testing.assert_allclose(got, k @ cf, atol=1e-4)


# ------------------------------------------------------------ flash_attention

class TestFlashAttention:
    @pytest.mark.parametrize("mask_kind,window", [("causal", 0), ("window", 64),
                                                  ("bidir", 0)])
    @pytest.mark.parametrize("t,s,h,hk,d", [
        (128, 128, 4, 4, 64),    # MHA, aligned
        (100, 100, 4, 2, 32),    # GQA, unaligned seq
        (1, 200, 8, 1, 64),      # decode: 1 query vs long kv (MQA)
    ])
    def test_matches_ref(self, mask_kind, window, t, s, h, hk, d):
        if mask_kind in ("causal", "window") and t > s:
            pytest.skip("query longer than kv is not a decode/prefill shape")
        rng = np.random.default_rng(8)
        q = jnp.asarray(rng.normal(size=(2, t, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, s, hk, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, s, hk, d)), jnp.float32)
        got = flash_attention(q, k, v, mask_kind=mask_kind, window=window,
                              force_pallas=True)
        want = fa_ref.flash_attention_ref(q, k, v, mask_kind, window)
        np.testing.assert_allclose(got, want, atol=2e-4)

    def test_bf16_close_to_f32_ref(self):
        rng = np.random.default_rng(9)
        q = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
        got = flash_attention(q, k, v, force_pallas=True)
        want = fa_ref.flash_attention_ref(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            "causal", 0)
        np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=3e-2)

    def test_window_equals_causal_when_window_covers_seq(self):
        rng = np.random.default_rng(10)
        q = jnp.asarray(rng.normal(size=(1, 96, 2, 32)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 96, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 96, 2, 32)), jnp.float32)
        a = flash_attention(q, k, v, mask_kind="window", window=96, force_pallas=True)
        b = flash_attention(q, k, v, mask_kind="causal", force_pallas=True)
        np.testing.assert_allclose(a, b, atol=1e-5)

    def test_causal_first_row_attends_self_only(self):
        rng = np.random.default_rng(11)
        q = jnp.asarray(rng.normal(size=(1, 130, 1, 32)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 130, 1, 32)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 130, 1, 32)), jnp.float32)
        out = flash_attention(q, k, v, mask_kind="causal", force_pallas=True)
        np.testing.assert_allclose(out[0, 0, 0], v[0, 0, 0], atol=1e-5)
