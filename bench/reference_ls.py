"""The plain reference that decides ``correct`` for least-squares cells.  It
imports nothing of the program and takes nothing it made: only the rows,
targets, grids and fold keys that the benchmark itself drew.

:func:`cv_cell` is liquidSVM's k-fold CV of kernel ridge regression for one
working set, written out step by step:

1. the squared distances of the padded set, its Gram ``exp(-D²/γ²)`` per γ;
2. for each fold and each λ one direct dense solve of
   ``(K_tt + λ·n_tr·I) c = y_t`` over the fold's ``n_tr`` training rows
   (``jnp.linalg.solve``: LU with partial pivoting), the fold's ten λ as
   one batch of ten systems, so that one fold and one γ fit at a time.
   The system is written at the padded size, with the identity's rows and
   columns outside the training rows: it is block-diagonal, so the
   training block's solution is the same, those rows' is 0, and every
   cell and fold solves at one shape;
3. the validation rows' predictions ``K_vt c`` and their mean squared
   error, the mean over folds giving the validation surface;
4. the argmin: per γ the first least λ, then strictly better γ only.

Every matmul runs at ``PRECISION`` (``HIGHEST``), and the solves under
``jax.default_matmul_precision`` of the same name.  The control
(``bench/control_ls.py``) loads its own copy of this module with
``PRECISION`` at ``DEFAULT`` (one bf16 pass on a TPU) and puts it in the
program's place; the comparison has to refuse it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = jax.lax.Precision.HIGHEST


def fold_masks(key, mask, n_folds: int):
    """(n_folds, n) validation membership: a uniform draw per row from the
    cell's fold key, rows ranked by it, rank mod n_folds (padding: none)."""
    u = jax.random.uniform(key, mask.shape)
    u = jnp.where(mask > 0, u, jnp.inf)
    rank = jnp.argsort(jnp.argsort(u))
    fold_of = jnp.where(mask > 0, rank % n_folds, -1)
    return np.asarray(fold_of[None, :] == jnp.arange(n_folds)[:, None])


def _sq_dists(x):
    sq = jnp.sum(x * x, axis=-1)
    return jnp.maximum(sq[:, None] + sq[None, :]
                       - 2.0 * jnp.matmul(x, x.T, precision=PRECISION), 0.0)


def _fold(k, y, tr, va, lambdas):
    """One fold at one γ: ``tr``, ``va`` (n,) 0/1 masks.  The ten solves
    and their validation MSE."""
    n = k.shape[0]
    n_tr = jnp.sum(tr)
    a = k * tr[:, None] * tr[None, :] + jnp.diag(1.0 - tr)
    a = a[None] + (lambdas * n_tr)[:, None, None] * jnp.eye(n)[None]
    with jax.default_matmul_precision(PRECISION.name.lower()):
        c = jnp.linalg.solve(a, jnp.broadcast_to((y * tr)[None, :, None],
                                                 (len(lambdas), n, 1)))
    c = c[:, :, 0].T                                              # (n, L)
    f = jnp.matmul(k, c, precision=PRECISION)                     # (n, L)
    mse = jnp.sum(va[:, None] * (y[:, None] - f) ** 2, axis=0) / jnp.sum(va)
    return mse, c


def cv_cell(x, y, mask, gammas, lambdas, fold_key, *, n_folds: int = 5) -> dict:
    """One working set's CV.  ``x`` (n, d) f32 padded rows, ``y`` (n,) the
    centred target (0 on padding), ``mask`` (n,), ``gammas`` (G,),
    ``lambdas`` (L,).

    Returns host arrays: ``surface`` (G, L) mean validation MSE, ``coefs``
    (G, n, L) fold-averaged models at every grid point (0 off each fold's
    training rows), and the argmin's ``g_idx``, ``l_idx``."""
    x, y = jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)
    mask = np.asarray(mask) > 0
    lambdas = jnp.asarray(lambdas, jnp.float32)
    val = fold_masks(jnp.asarray(fold_key), jnp.asarray(mask, jnp.float32),
                     n_folds)
    folds = [(jnp.asarray(~v & mask, jnp.float32), jnp.asarray(v, jnp.float32))
             for v in val]
    d2 = jax.jit(_sq_dists)(x)
    fold = jax.jit(_fold)
    n = x.shape[0]
    surface, coefs = [], []
    for g in np.asarray(gammas, np.float32):
        k = jnp.exp(-d2 / jnp.float32(g * g))
        loss, avg = 0.0, np.zeros((n, lambdas.shape[0]))
        for tr, va in folds:
            mse, c = fold(k, y, tr, va, lambdas)
            loss = loss + np.asarray(mse, np.float64)
            avg += np.asarray(c, np.float64)
        surface.append(loss / n_folds)
        coefs.append(avg / n_folds)
    surface = np.stack(surface)
    best, g_idx, l_idx = np.inf, 0, 0
    for g in range(surface.shape[0]):
        l_star = int(np.argmin(surface[g]))
        if surface[g, l_star] < best:
            best, g_idx, l_idx = surface[g, l_star], g, l_star
    return {"surface": surface, "coefs": np.stack(coefs),
            "g_idx": g_idx, "l_idx": l_idx}
