"""Pallas TPU kernels: fused SVM test-phase evaluation.

liquidSVM parallelizes "evaluating the SVM models on the test data" (CPU
threads + CUDA).  TPU adaptation: never materialize K(test, SV) in HBM —
each (bt x bs) Gram tile is produced in VMEM (MXU cross term + VPU exp)
and immediately contracted against the coefficient block (MXU again),
accumulating f = K @ C tile-by-tile.  Arithmetic intensity rises from
O(1) (Gram write + later GEMV read) to O(bs) per Gram element.

Grid (n_test/bt, n_sv/bs): the sv axis is the sequential inner dimension;
the output tile is revisited and accumulated across it.

``svm_predict_cells_pallas`` is the serving-engine launch: ONE kernel over a
whole batch of routed cells (grid (C, n_test/bt, n_sv/bs)), where each cell
carries P = n_tasks * n_sub coefficient columns and every column its own
selected gamma.  The gamma-independent D² tile is computed once per (bt, bs)
block and each column replays only the cheap exp epilogue against it — the
distance-cache factorization applied inside VMEM, so a multi-task multi-
gamma model bank pays the MXU cross term exactly once per tile per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

BLOCK_T = 128   # test rows per tile
BLOCK_S = 128   # support vectors per tile


def _predict_kernel(x_ref, sv_ref, c_ref, gamma_ref, o_ref, *, kind: str):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)     # (bt, d)
    sv = sv_ref[...].astype(jnp.float32)   # (bs, d)
    gamma = gamma_ref[0, 0]
    cross = jax.lax.dot_general(x, sv, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    d2 = jnp.maximum(jnp.sum(x * x, -1)[:, None] + jnp.sum(sv * sv, -1)[None, :]
                     - 2.0 * cross, 0.0)
    if kind == "gauss_rbf":
        k_tile = jnp.exp(-d2 / jnp.maximum(gamma * gamma, 1e-12))
    elif kind == "laplacian":
        k_tile = jnp.exp(-jnp.sqrt(d2 + 1e-12) / jnp.maximum(gamma, 1e-12))
    else:
        raise ValueError(kind)
    partial = jnp.dot(k_tile, c_ref[...].astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)  # (bt, P)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(j > 0)
    def _acc():
        o_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def svm_predict_pallas(x_test: Array, sv: Array, coefs: Array, gamma: Array,
                       kind: str = "gauss_rbf", interpret: bool = True) -> Array:
    """x_test (nt, d), sv (ns, d), coefs (ns, P); nt % 128 == ns % 128 == 0."""
    nt, d = x_test.shape
    ns, p = sv.shape[0], coefs.shape[1]
    gamma_arr = jnp.reshape(jnp.asarray(gamma, jnp.float32), (1, 1))
    return pl.pallas_call(
        functools.partial(_predict_kernel, kind=kind),
        grid=(nt // BLOCK_T, ns // BLOCK_S),
        in_specs=[
            pl.BlockSpec((BLOCK_T, d), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_S, d), lambda i, j: (j, 0)),
            pl.BlockSpec((BLOCK_S, p), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_T, p), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nt, p), jnp.float32),
        interpret=interpret,
    )(x_test, sv, coefs, gamma_arr)


def _predict_cells_kernel(x_ref, sv_ref, c_ref, g_ref, o_ref, *, kind: str):
    """One routed cell tile: D² once, per-column gamma epilogue + contract.

    Padded SV rows carry zero coefficients (exact zero contribution) and
    padded cells zero coefficient blocks, so no masking is needed; padded
    test rows produce garbage sliced off by the wrapper.
    """
    j = pl.program_id(2)
    x = x_ref[0].astype(jnp.float32)       # (bt, d)
    sv = sv_ref[0].astype(jnp.float32)     # (bs, d)
    c = c_ref[0].astype(jnp.float32)       # (bs, P)
    cross = jax.lax.dot_general(x, sv, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    d2 = jnp.maximum(jnp.sum(x * x, -1)[:, None] + jnp.sum(sv * sv, -1)[None, :]
                     - 2.0 * cross, 0.0)
    cols = []
    for p in range(c.shape[1]):            # static P, small (n_tasks * n_sub)
        gamma = g_ref[0, 0, p]
        if kind == "gauss_rbf":
            k_tile = jnp.exp(-d2 / jnp.maximum(gamma * gamma, 1e-12))
        elif kind == "laplacian":
            k_tile = jnp.exp(-jnp.sqrt(d2 + 1e-12) / jnp.maximum(gamma, 1e-12))
        else:
            raise ValueError(kind)
        cols.append(jnp.dot(k_tile, c[:, p:p + 1],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32))
    partial = jnp.concatenate(cols, axis=1)  # (bt, P)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = partial[None]

    @pl.when(j > 0)
    def _acc():
        o_ref[...] += partial[None]


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def svm_predict_cells_pallas(xt: Array, sv: Array, coefs: Array, gammas: Array,
                             kind: str = "gauss_rbf",
                             interpret: bool = True) -> Array:
    """xt (C, nt, d), sv (C, ns, d), coefs (C, ns, P), gammas (C, P).

    Returns (C, nt, P) f32; nt % 128 == ns % 128 == 0.  One launch covers
    every active cell of a serving step — the cell axis is the outer grid
    dimension, so each cell's SV tiles stream through VMEM exactly once.
    """
    n_cells, nt, d = xt.shape
    ns, p = sv.shape[1], coefs.shape[2]
    g3 = jnp.asarray(gammas, jnp.float32).reshape(n_cells, 1, p)
    return pl.pallas_call(
        functools.partial(_predict_cells_kernel, kind=kind),
        grid=(n_cells, nt // BLOCK_T, ns // BLOCK_S),
        in_specs=[
            pl.BlockSpec((1, BLOCK_T, d), lambda c, i, j: (c, i, 0)),
            pl.BlockSpec((1, BLOCK_S, d), lambda c, i, j: (c, j, 0)),
            pl.BlockSpec((1, BLOCK_S, p), lambda c, i, j: (c, j, 0)),
            pl.BlockSpec((1, 1, p), lambda c, i, j: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_T, p), lambda c, i, j: (c, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cells, nt, p), jnp.float32),
        interpret=interpret,
    )(xt, sv, coefs, g3)
