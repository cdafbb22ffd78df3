"""Batched box-constrained QP engine — the heart of every liquidSVM solver.

Every non-smooth liquidSVM dual (hinge, weighted hinge, pinball) is

    min_c   0.5 c^T K c  -  c^T y      s.t.   lo <= c <= hi      (coordinatewise)

in *coefficient space* ``c`` (f = sum_i c_i k(x_i, .)).  Crucially the
objective does not depend on lambda at all: lambda (and the weight w) only
move the box.  So the whole hyper-parameter grid is solved as **columns of
one matrix iteration**: C is (n, P) for P = |lambda-grid| x |w-grid| and the
per-iteration cost is one GEMM ``K @ C`` — this is how liquidSVM's
"kernel-matrix re-use + warm starts" becomes MXU-native.

The iteration is FISTA (accelerated projected gradient) with gradient-based
adaptive restart; the step is 1/L with L from power iteration (shared across
all columns, K is shared).  liquidSVM's sequential 2D-working-set CD is
latency-bound on a systolic machine; block/batched first-order iterations
reach the same KKT point (asserted in tests) with matmul-shaped work.

Stopping: projected-gradient (KKT) residual, uniform across solvers:
``r = || c - clip(c - g, lo, hi) ||_inf`` with ``g = K c - y``.  It is
checked once per block of ``check_every`` steps: an outer ``while_loop``
over blocks, an inner loop of ``check_every`` plain FISTA steps, then one
check, which costs a second ``K @ C``.  So a solve stops at a multiple of
``check_every`` or at ``max_iters``.  The check sits outside the step on
purpose: every train path vmaps the solve over folds (and slots), which
batches the loop's carry, and a ``lax.cond`` on a batched predicate runs
both branches, every operand (K too) broadcast to the batch.  A check
inside the step would cost that second GEMM on every iteration, reading K
once per fold, instead of once in ``check_every`` iterations.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class BoxQPResult(NamedTuple):
    c: Array          # (n, P) solution
    kkt: Array        # (P,) final KKT residual per column
    iters: Array      # () iterations used
    l_est: Array      # () estimated Lipschitz constant


def _kdot(k_mat: Array, c: Array) -> Array:
    """K @ C in K's storage dtype with f32 accumulation (bf16 Gram path:
    the MXU reads 2-byte tiles, accumulates f32 — §Perf SVM hillclimb).
    An f32 Gram is multiplied in full f32, not the TPU's one-pass bf16."""
    return jax.lax.dot_general(
        k_mat, c.astype(k_mat.dtype), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def power_iteration_l(k_mat: Array, iters: int = 32, seed: int = 0) -> Array:
    """Largest eigenvalue of PSD K (safety-factored), shared across columns."""
    n = k_mat.shape[-1]
    v = jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32)

    def body(_, v):
        w = _kdot(k_mat, v[:, None])[:, 0]
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v)
    lam = jnp.dot(v, _kdot(k_mat, v[:, None])[:, 0],
                  precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(lam, 1e-12) * 1.05


def kkt_residual(c: Array, g: Array, lo: Array, hi: Array) -> Array:
    """Projected-gradient residual per column, scaled by the box width."""
    r = c - jnp.clip(c - g, lo, hi)
    width = jnp.maximum(jnp.max(hi - lo, axis=0), 1e-30)
    return jnp.max(jnp.abs(r), axis=0) / width


def clip_warm_start(c0: Array, lo: Array, hi: Array) -> Array:
    """Project a warm start into a column's feasible box.

    The grid-neighbor solution being reused generally lives in a DIFFERENT
    box (lambda and the class weight scale it; a moved select-phase winner
    may change both), so the projection is mandatory before any solver
    touches it: the FISTA iteration below requires ``lo <= c0 <= hi``, and
    from any feasible start it converges to the same box-QP optimum as
    the cold ``c0 = 0`` it replaces.
    """
    return jnp.clip(c0, lo, hi)


def box_qp(
    k_mat: Array,
    y: Array,
    lo: Array,
    hi: Array,
    c0: Array | None = None,
    tol: float = 1e-3,
    max_iters: int = 2000,
    l_est: Array | None = None,
    check_every: int = 10,
) -> BoxQPResult:
    """Solve min 0.5 c^T K c - c^T y, lo <= c <= hi for all columns at once.

    Shapes: k_mat (n, n); y (n,) or (n, P); lo/hi broadcastable to (n, P);
    c0 warm start (n, P).  Returns f32 everywhere.  k_mat may be bf16
    (read-optimized Gram); all accumulation stays f32.
    """
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
    c0 = clip_warm_start(c0, lo, hi)  # warm starts from a larger box are clipped in

    if l_est is None:
        l_est = power_iteration_l(k_mat)
    step = 1.0 / l_est

    def grad(c):
        return _kdot(k_mat, c) - y

    def fista_step(_, state):
        c, z, t = state
        g = grad(z)
        c_new = jnp.clip(z - step * g, lo, hi)
        # gradient-based adaptive restart (O'Donoghue & Candes)
        restart = jnp.sum(g * (c_new - c)) > 0.0
        t_new = jnp.where(restart, 1.0, 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)))
        beta = jnp.where(restart, 0.0, (t - 1.0) / t_new)
        z_new = c_new + beta * (c_new - c)
        return c_new, z_new, t_new

    def running(last):
        return lambda state: jnp.logical_and(state[3] < last, jnp.max(state[4]) > tol)

    def block(state):
        c, z, t, it, _ = state
        c, z, t = jax.lax.fori_loop(0, check_every, fista_step, (c, z, t))
        return c, z, t, it + check_every, kkt_residual(c, grad(c), lo, hi)

    def single(state):
        c, z, t, it, res = state
        c, z, t = fista_step(0, (c, z, t))
        return c, z, t, it + 1, res

    # Whole blocks of check_every steps, each checked once at its end; then
    # the max_iters % check_every steps left, unchecked, for lanes still running.
    blocks_end = (max_iters // check_every) * check_every
    init = (c0, c0, jnp.float32(1.0), jnp.int32(0), jnp.full((p,), jnp.inf, jnp.float32))
    state = jax.lax.while_loop(running(blocks_end), block, init)
    c, _, _, it, _ = jax.lax.while_loop(running(max_iters), single, state)
    final_res = kkt_residual(c, grad(c), lo, hi)
    return BoxQPResult(c=c, kkt=final_res, iters=it, l_est=l_est)


def dual_objective(k_mat: Array, y: Array, c: Array) -> Array:
    """-(0.5 c^T K c - c^T y) per column — monotone diagnostics / tests."""
    if y.ndim == 1:
        y = y[:, None]
    kc = jnp.matmul(k_mat, c, precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(c * y, axis=0) - 0.5 * jnp.sum(c * kc, axis=0)
