"""Least-squares solver (kernel ridge regression) — liquidSVM's LS path.

Primal: min_f lambda ||f||^2 + (1/n) sum (y_i - f(x_i))^2.  Stationarity
gives (K + lambda n I) c = y on the training coordinates.

The CV (:func:`solve_columns`) takes one Cholesky per lambda
(:func:`solve_krr_chol`): XLA's Cholesky is batched over the CV's vmapped
(slot, fold) lanes, compiles in seconds and costs n^3 / 3 per lambda, and
in f32 it does not break down at liquidSVM's smallest lambda (lambda n
~ 1e-4 against ||K|| ~ n).  An eigendecomposition would sweep the whole
lambda path as a diagonal rescale, but JAX's TPU eigh (QDWH, spectral
divide and conquer) maps over every batch axis, so the vmapped eighs
would run one after another, and one eigh at n = 2853 compiles for
minutes into ~0.9 GB of device code.

Compaction: a fold's validation rows and the cell's padding rows carry
no part of its system, so :func:`solve_columns` gathers the fold's
training rows (in their order) into a block of side n_c before the
lambda loop and scatters c back after it: the Cholesky costs n_c^3 / 3
and its triangular solves n_c^2, not n^3 / 3 and n^2.  The block is a
static shape, so n_c must hold the most training rows any fold can have:
the CV passes ``cv.ls_factor_side(n, n_folds)``, which is
``cv.fold_train_bound`` = n - n // n_folds (every scheme of
``cv.make_fold_masks`` puts at least n_real // n_folds of a cell's
n_real <= n real rows in each fold's validation part) rounded up to whole
128-column panels (n = 2853, 5 folds: 2304).  Padding rows vary from cell
to cell and stay in the block, masked, as do the rows the rounding adds.

Masking: with M = diag(train_mask), the Cholesky of M K M + lambda n I
solves the fold subproblem exactly — padded coordinates see
(0 + lambda n) c = 0 => c = 0, and the factor keeps them apart exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def _masked(k_mat: Array, train_mask: Array | None) -> Array:
    if train_mask is None:
        return k_mat
    m = train_mask.astype(k_mat.dtype)
    return k_mat * m[:, None] * m[None, :]


def solve_columns(k_mat: Array, y: Array, lambdas: Array, n_eff: Array,
                  train_mask: Array, n_c: int) -> Array:
    """The CV's least-squares solve: every column of y (n, P) at its lambda
    (P,) on one fold's training rows, one :func:`solve_krr_chol` per
    column.  The training rows are gathered first (in their order) into an
    ``n_c``-row block, which must hold them all; the factor and its path
    see only that block, and the block's other rows stay masked.  The
    columns run one after another (``lax.map``), so a vmapped caller holds
    one ``n_c x n_c`` factor per lane.  Returns c (n, P), 0 outside
    ``train_mask``."""
    with jax.named_scope("cv.ls_factor"):
        rows = jnp.argsort((train_mask == 0).astype(jnp.int32),
                           stable=True)[:n_c]
        m = train_mask[rows].astype(jnp.float32)
        km = _masked(k_mat[rows][:, rows].astype(jnp.float32), m)
    with jax.named_scope("cv.ls_path"):
        y = (y[rows].astype(jnp.float32) * m[:, None]).T
    n_eff = jnp.broadcast_to(n_eff, lambdas.shape)
    c = jax.lax.map(lambda col: solve_krr_chol(km, *col), (y, lambdas, n_eff))
    with jax.named_scope("cv.ls_path"):
        return jnp.zeros((k_mat.shape[0], c.shape[0]), jnp.float32) \
            .at[rows].set(c.T, unique_indices=True)


def solve_krr_chol(
    k_mat: Array,
    y: Array,
    lam: Array,
    n_eff: Array,
    train_mask: Array | None = None,
) -> Array:
    """Single-lambda Cholesky path; the CV runs it once per column
    (:func:`solve_columns`).  Reads the lower triangle of the symmetric
    ``k_mat``.  XLA's Cholesky and triangular solves compute their
    products at ``HIGHEST``."""
    km = _masked(k_mat.astype(jnp.float32), train_mask)
    y = y.astype(jnp.float32)
    if train_mask is not None:
        y = y * train_mask.astype(jnp.float32)
    n = km.shape[0]
    with jax.named_scope("cv.ls_factor"):
        a = km + (lam * jnp.maximum(n_eff, 1.0)) * jnp.eye(n, dtype=jnp.float32)
        # K is symmetric: (A + A^T) / 2 would cost a transposed copy of
        # the fold matrix, ~10 % of an ls wave's device time on a v5e
        low = jax.lax.linalg.cholesky(a, symmetrize_input=False)
    with jax.named_scope("cv.ls_path"):
        return jax.scipy.linalg.cho_solve((low, True), y)
