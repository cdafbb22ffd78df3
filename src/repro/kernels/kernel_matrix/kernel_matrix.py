"""Pallas TPU kernels: tiled Gram-matrix computation and the split
distance-cache pipeline.

liquidSVM's single hottest loop ("routines for computing the kernel
matrices ... parallelized ... Cuda implementations").  TPU adaptation: the
cross term -2*X@Z^T is an MXU matmul; the squared norms + exp are VPU
epilogue fused in the same VMEM tile, so each (bn x bm) output tile is
written exactly once to HBM.

The CV grid scan needs the Gram for MANY gammas over the SAME points, and
the expensive part — the pairwise squared-distance matrix D² — is
gamma-independent.  So the fused ``gram_pallas`` is complemented by a split
pipeline:

  * ``sq_dists_pallas``     writes D² once.  For the symmetric train Gram it
                            runs the MXU only on upper-triangle tiles
                            (i <= j) and writes the MIRRORED tile from inside
                            the kernel: a two-phase grid (i, j, m) keeps the
                            just-computed tile in VMEM scratch and the m == 1
                            phase stores its transpose at block (j, i).  ~2x
                            fewer MXU flops, a bitwise-symmetric result, and
                            no ``U + U.T`` combine — the old wrapper-side
                            mirror cost one extra full read + write of the
                            n² matrix in HBM;
  * ``gram_from_d2_pallas`` replays the cheap per-gamma VPU epilogue
                            (exp(-d2/gamma²) or Laplacian, optional bf16
                            downcast) over the cached D², one VMEM pass per
                            tile, no MXU work.

Tiling: grid (n/bn, m/bm); X tile (bn, d) and Z tile (bm, d) stream through
VMEM with d kept whole (SVM feature dims are small: d <= ~1k).  All dims
padded to the 128 lane width by ops.py; zero-padded features do not change
distances.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

BLOCK_N = 128
BLOCK_M = 128


def _gram_kernel(x_ref, z_ref, gamma_ref, o_ref, *, kind: str):
    gamma = gamma_ref[0, 0]
    d2 = _d2_tile(x_ref, z_ref)
    if kind == "gauss_rbf":
        o_ref[...] = jnp.exp(-d2 / jnp.maximum(gamma * gamma, 1e-12))
    elif kind == "laplacian":
        o_ref[...] = jnp.exp(-jnp.sqrt(d2 + 1e-12) / jnp.maximum(gamma, 1e-12))
    else:
        raise ValueError(kind)


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def gram_pallas(x: Array, z: Array, gamma: Array, kind: str = "gauss_rbf",
                interpret: bool = True) -> Array:
    """x (n, d), z (m, d) with n, m multiples of 128; returns K (n, m) f32."""
    n, d = x.shape
    m, _ = z.shape
    assert n % BLOCK_N == 0 and m % BLOCK_M == 0, (n, m)
    gamma_arr = jnp.reshape(jnp.asarray(gamma, jnp.float32), (1, 1))
    return pl.pallas_call(
        functools.partial(_gram_kernel, kind=kind),
        grid=(n // BLOCK_N, m // BLOCK_M),
        in_specs=[
            pl.BlockSpec((BLOCK_N, d), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_M, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N, BLOCK_M), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(x, z, gamma_arr)


def _d2_tile(x_ref, z_ref) -> Array:
    x = x_ref[...].astype(jnp.float32)          # (bn, d)
    z = z_ref[...].astype(jnp.float32)          # (bm, d)
    cross = jax.lax.dot_general(                # MXU: (bn, d) x (bm, d)^T
        x, z, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32
    )
    xx = jnp.sum(x * x, axis=-1)[:, None]
    zz = jnp.sum(z * z, axis=-1)[None, :]
    return jnp.maximum(xx + zz - 2.0 * cross, 0.0)


def _sq_dists_kernel(x_ref, z_ref, o_ref):
    o_ref[...] = _d2_tile(x_ref, z_ref)


def _sq_dists_sym_kernel(x_ref, z_ref, o_ref, acc_ref):
    """Two-phase symmetric tile: m == 0 computes the upper tile (i <= j) and
    parks it in VMEM scratch; m == 1 writes the transpose to block (j, i).
    Diagonal tiles are bitwise symmetric (same dot-product order both ways),
    so the m == 1 rewrite of (i, i) stores identical bits.  Strictly-lower
    iterations (i > j) do no compute and their output window is parked on
    the diagonal block (see ``_sym_out_map``), which a later phase of row i
    fully overwrites — every block is written exactly once with real data
    and the MXU runs only on the n_tiles*(n_tiles+1)/2 upper tiles.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    m = pl.program_id(2)

    @pl.when((i <= j) & (m == 0))
    def _compute():
        d2 = _d2_tile(x_ref, z_ref)
        acc_ref[...] = d2
        o_ref[...] = d2

    @pl.when((i <= j) & (m == 1))
    def _mirror():
        o_ref[...] = acc_ref[...].T


def _sym_out_map(i, j, m):
    """Upper tiles: (i, j) then the mirrored (j, i).  Lower iterations park
    on (i, i) so the window index stays constant across the skipped stretch
    (no spurious HBM writebacks between real visits)."""
    up = i <= j
    r = jnp.where(up, jnp.where(m == 0, i, j), i)
    c = jnp.where(up, jnp.where(m == 0, j, i), i)
    return r, c


@functools.partial(jax.jit, static_argnames=("symmetric", "interpret"))
def sq_dists_pallas(x: Array, z: Array, symmetric: bool = False,
                    interpret: bool = True) -> Array:
    """Tiled pairwise D²; n, m multiples of 128; returns (n, m) f32.

    ``symmetric=True`` requires x.shape == z.shape (callers pass x twice):
    the MXU runs only on the n_tiles*(n_tiles+1)/2 upper tiles and each
    tile's transpose is written to the mirrored block from INSIDE the kernel
    (two-phase grid + VMEM scratch) — the result is K == K.T bitwise with no
    post-hoc ``U + U.T`` pass over HBM.
    """
    n, d = x.shape
    m, _ = z.shape
    assert n % BLOCK_N == 0 and m % BLOCK_M == 0, (n, m)
    if not symmetric:
        return pl.pallas_call(
            _sq_dists_kernel,
            grid=(n // BLOCK_N, m // BLOCK_M),
            in_specs=[
                pl.BlockSpec((BLOCK_N, d), lambda i, j: (i, 0)),
                pl.BlockSpec((BLOCK_M, d), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((BLOCK_N, BLOCK_M), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
            interpret=interpret,
        )(x, z)

    # the tile predicate i <= j only matches the matrix upper triangle
    # when tiles are square — guard against a BLOCK_M-only perf tweak
    assert n == m and BLOCK_N == BLOCK_M, (n, m, BLOCK_N, BLOCK_M)
    return pl.pallas_call(
        _sq_dists_sym_kernel,
        grid=(n // BLOCK_N, m // BLOCK_M, 2),
        in_specs=[
            pl.BlockSpec((BLOCK_N, d), lambda i, j, m: (i, 0)),
            pl.BlockSpec((BLOCK_M, d), lambda i, j, m: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N, BLOCK_M), _sym_out_map),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BLOCK_N, BLOCK_M), jnp.float32)],
        interpret=interpret,
    )(x, z)


def _gram_from_d2_kernel(d2_ref, gamma_ref, o_ref, *, kind: str):
    d2 = d2_ref[...].astype(jnp.float32)
    gamma = gamma_ref[0, 0]
    if kind == "gauss_rbf":
        k = jnp.exp(-d2 / jnp.maximum(gamma * gamma, 1e-12))
    elif kind == "laplacian":
        k = jnp.exp(-jnp.sqrt(d2 + 1e-12) / jnp.maximum(gamma, 1e-12))
    else:
        raise ValueError(kind)
    o_ref[...] = k.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kind", "out_dtype", "interpret"))
def gram_from_d2_pallas(d2: Array, gamma: Array, kind: str = "gauss_rbf",
                        out_dtype: str = "f32", interpret: bool = True) -> Array:
    """Per-gamma epilogue over a cached D²: exp + optional bf16 downcast in
    one VMEM pass per (bn, bm) tile.  Pure VPU work — the whole point is
    that the CV gamma scan replays THIS instead of the MXU cross-term.
    """
    n, m = d2.shape
    assert n % BLOCK_N == 0 and m % BLOCK_M == 0, (n, m)
    dtype = jnp.bfloat16 if out_dtype == "bf16" else jnp.float32
    gamma_arr = jnp.reshape(jnp.asarray(gamma, jnp.float32), (1, 1))
    return pl.pallas_call(
        functools.partial(_gram_from_d2_kernel, kind=kind),
        grid=(n // BLOCK_N, m // BLOCK_M),
        in_specs=[
            pl.BlockSpec((BLOCK_N, BLOCK_M), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N, BLOCK_M), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), dtype),
        interpret=interpret,
    )(d2, gamma_arr)
