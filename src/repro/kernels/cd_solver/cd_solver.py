"""Pallas TPU kernels: in-VMEM Gauss-Seidel coordinate-descent epochs —
per-cell and fused across a whole training wave.

The faithful port of liquidSVM's "carefully implemented" sequential solver
(Steinwart–Hush–Scovel 1D working sets).  TPU adaptation:

* the Gram matrix streams through VMEM one (n x B) column-block at a time;
  the sequential TPU grid over blocks IS the Gauss–Seidel order;
* the dual state (c, g, lo, hi) lives in VMEM for the whole epoch via
  input/output aliasing (index_map pins them to one block);
* each 1-D step is batched over the P hyper-parameter-grid columns: the
  rank-1 gradient maintenance g += K[:, i] (x) delta is a (n x P) VPU op, so
  the machine is busy even though coordinates are sequential.

Wave fusion contract (``cd_wave_epoch_pallas``)
-----------------------------------------------
Training solves a WAVE of packed cell slots at a time
(``repro.distributed.cell_trainer.train_cells_waves``); launching the CD
kernel once per slot serializes S kernel dispatches and re-stages state
per launch.  The wave variant is ONE ``pallas_call`` over grid
``(S, n // B)``:

* the slot axis is the outer grid dimension — embarrassingly parallel
  (``dimension_semantics=("parallel", "arbitrary")``), so Mosaic may run
  slots concurrently while the inner block axis stays sequential
  (Gauss–Seidel order within a slot is preserved exactly);
* slot ``s``'s Gram tiles ``K_s[:, jB:(j+1)B]`` stream through VMEM while
  its dual state ``(c_s, g_s, lo_s, hi_s)`` stays RESIDENT across the
  whole ``j`` sweep (index_map pins the state block per slot; c/g are
  input/output-aliased) — the ``kernels/kernel_matrix`` residency idiom
  extended from one cell to the wave;
* slot-major grid order means each slot's state is touched by a single
  contiguous run of grid steps, so the per-slot coordinate sequence is
  bit-identical to the per-slot kernel (asserted in
  ``tests/test_kernels.py::TestCDWave``).

Off TPU, ``ops.cd_epochs_wave`` runs the same wave fusion through
``ref.cd_epoch_wave_blocked_ref`` instead: LAPACK-style delayed trailing
updates (sweep a ``WAVE_BLOCK`` panel keeping only the block-local
gradient consistent, then land the trailing update as one batched GEMM).
Same coordinate order and fixed point, but the summation order differs —
that path matches the exact sweep to f32 rounding (within solver
tolerance), not bitwise; only the TPU Pallas wave keeps per-slot
bit-identity.

Warm-start contract
-------------------
The kernel polishes whatever ``c0`` it is given: the caller passes the
gradient ``g0 = K c0 - y`` consistent with that start.  Across the
hyper-parameter grid the right ``c0`` is the NEIGHBORING grid column's
solution, box-clipped into the new column's feasible box
(``repro.core.solvers.base.clip_warm_start``) — a clipped feasible start
plus Gauss–Seidel's monotone descent means every epoch only improves the
dual, so warm starts can never do worse than the cold ``c0 = 0`` they
replace.  ``repro.core.cv`` owns the grid-neighbor bookkeeping (gamma-scan
carry + select-phase cached columns); this module only requires
``lo <= c0 <= hi``.

Padding: coordinates past a cell's true size carry ``lo == hi == 0`` —
the clip pins them at 0 and their rank-1 update is exactly zero, so padded
slots/rows are inert (the planner's empty slots solve to all-zeros).

Used as a high-accuracy polishing pass after the batched FISTA solver
(``repro.core.solvers.base``) — FISTA owns the MXU-shaped bulk work; one
CD epoch costs the same n²P flops as ONE FISTA iteration but sweeps every
coordinate exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

BLOCK_COORDS = 128  # coordinates per grid step (column-block width)


def _cd_body(k_blk, diag_ref, lo_ref, hi_ref, c_ref, g_ref, base,
             block: int):
    """Sweep coordinates [base, base + block) of one cell's state refs.

    k_blk (n, block) is the Gram column block already read into registers;
    diag_ref (1, n); lo/hi/c/g refs (n, P).  Mosaic cannot slice a value at
    a dynamic lane offset, so coordinate t's Gram column and diagonal entry
    are picked with a one-hot lane select + lane sum (exact: one nonzero
    term), and the diagonal block is read at a 128-aligned offset.
    """
    diag = diag_ref[:, pl.ds(pl.multiple_of(base, block), block)]  # (1, block)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)

    def body(t, _):
        i = base + t
        hit = lane == t
        d = jnp.maximum(jnp.sum(jnp.where(hit, diag, 0.0), axis=1,
                                keepdims=True), 1e-12)            # (1, 1)
        row = (pl.ds(i, 1), slice(None))
        ci = c_ref[row]                                           # (1, P)
        gi = g_ref[row]
        li = lo_ref[row]
        hi = hi_ref[row]
        target = jnp.clip(ci - gi / d, li, hi)
        delta = target - ci                                       # (1, P)
        c_ref[row] = target
        k_col = jnp.sum(jnp.where(hit, k_blk, 0.0), axis=1,
                        keepdims=True)                            # (n, 1)
        g_ref[...] += k_col * delta
        return 0

    jax.lax.fori_loop(0, block, body, 0)


def _cd_kernel(k_blk_ref, diag_ref, lo_ref, hi_ref, c_in_ref, g_in_ref,
               c_ref, g_ref, *, block: int):
    """Grid step j sweeps coordinates [j*block, (j+1)*block)."""
    del c_in_ref, g_in_ref  # aliased into c_ref / g_ref
    j = pl.program_id(0)
    _cd_body(k_blk_ref[...], diag_ref, lo_ref, hi_ref, c_ref, g_ref,
             j * block, block)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cd_epoch_pallas(k_mat: Array, c: Array, g: Array, lo: Array, hi: Array,
                    interpret: bool = True) -> tuple[Array, Array]:
    """One epoch.  k_mat (n, n) with n % BLOCK_COORDS == 0; c/g/lo/hi (n, P)."""
    n, p = c.shape
    assert n % BLOCK_COORDS == 0, n
    diag = jnp.diag(k_mat).astype(jnp.float32)[None, :]  # (1, n)
    full = lambda i: (0, 0)
    c_out, g_out = pl.pallas_call(
        functools.partial(_cd_kernel, block=BLOCK_COORDS),
        grid=(n // BLOCK_COORDS,),
        in_specs=[
            pl.BlockSpec((n, BLOCK_COORDS), lambda j: (0, j)),   # Gram column block
            pl.BlockSpec((1, n), full),                          # diag
            pl.BlockSpec((n, p), full),                          # lo
            pl.BlockSpec((n, p), full),                          # hi
            pl.BlockSpec((n, p), full),                          # c (aliased out 0)
            pl.BlockSpec((n, p), full),                          # g (aliased out 1)
        ],
        out_specs=[pl.BlockSpec((n, p), full), pl.BlockSpec((n, p), full)],
        out_shape=[
            jax.ShapeDtypeStruct((n, p), jnp.float32),
            jax.ShapeDtypeStruct((n, p), jnp.float32),
        ],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(k_mat.astype(jnp.float32), diag, lo.astype(jnp.float32),
      hi.astype(jnp.float32), c.astype(jnp.float32), g.astype(jnp.float32))
    return c_out, g_out


def _cd_wave_kernel(k_blk_ref, diag_ref, lo_ref, hi_ref, c_in_ref, g_in_ref,
                    c_ref, g_ref, *, block: int):
    """Grid step (s, j): coordinates [j*block, (j+1)*block) of slot s.

    The leading slot axis is squeezed out of every block (block dim None),
    so the body is the per-cell sweep verbatim — slot s's state blocks are
    pinned across its whole j run by the index_map.
    """
    del c_in_ref, g_in_ref  # aliased into c_ref / g_ref
    j = pl.program_id(1)
    _cd_body(k_blk_ref[...], diag_ref, lo_ref, hi_ref, c_ref, g_ref,
             j * block, block)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cd_wave_epoch_pallas(k_mats: Array, c: Array, g: Array, lo: Array,
                         hi: Array, interpret: bool = True
                         ) -> tuple[Array, Array]:
    """One epoch over a whole wave in ONE launch.

    k_mats (S, n, n) with n % BLOCK_COORDS == 0; c/g/lo/hi (S, n, P).
    Per-slot semantics are identical to :func:`cd_epoch_pallas` (same
    coordinate order, same arithmetic — see the module docstring's wave
    fusion contract).
    """
    s, n, p = c.shape
    assert n % BLOCK_COORDS == 0, n
    diag = jnp.einsum("sii->si", k_mats).astype(jnp.float32)[:, None, :]
    state = lambda si, j: (si, 0, 0)                     # pinned per slot
    kwargs = {}
    if not interpret:  # Mosaic: slots are parallel, the block sweep is not
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    c_out, g_out = pl.pallas_call(
        functools.partial(_cd_wave_kernel, block=BLOCK_COORDS),
        grid=(s, n // BLOCK_COORDS),
        in_specs=[
            pl.BlockSpec((None, n, BLOCK_COORDS),
                         lambda si, j: (si, 0, j)),      # Gram column block
            pl.BlockSpec((None, 1, n), state),           # diag
            pl.BlockSpec((None, n, p), state),           # lo
            pl.BlockSpec((None, n, p), state),           # hi
            pl.BlockSpec((None, n, p), state),           # c (aliased out 0)
            pl.BlockSpec((None, n, p), state),           # g (aliased out 1)
        ],
        out_specs=[pl.BlockSpec((None, n, p), state),
                   pl.BlockSpec((None, n, p), state)],
        out_shape=[
            jax.ShapeDtypeStruct((s, n, p), jnp.float32),
            jax.ShapeDtypeStruct((s, n, p), jnp.float32),
        ],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        **kwargs,
    )(k_mats.astype(jnp.float32), diag, lo.astype(jnp.float32),
      hi.astype(jnp.float32), c.astype(jnp.float32), g.astype(jnp.float32))
    return c_out, g_out
