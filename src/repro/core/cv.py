"""k-fold cross-validation driver (liquidSVM §2 "Hyper-Parameter Selection").

Execution shape (the whole point of the TPU port):

    D2 = sq_dists(X, X)                    # ONE distance matrix per cell —
                                           #   the only O(n²d) MXU cross term
                                           #   in the whole gamma scan
    for gamma in gammas:                   # lax.scan — Gram re-use
        K = epilogue(D2, gamma)            # O(n²) VPU pass: exp(-D2/gamma²),
                                           #   bf16 downcast fused on write;
                                           #   shared by all folds, all TASKS,
                                           #   and the full lambda/tau/w grid
        for fold in folds:                 # vmap — "multi-threading"
            solve ALL columns (task x lambda x tau/w) as one batched box-QP
            validation predictions = K @ C             (one GEMM)
        streaming selection: keep the per-(task, sub) best model so far

Distance-cache pipeline: both built-in kernels factor through the
gamma-independent D², so the Gram rematerialization cost across an n_gamma
grid drops from n_gamma GEMMs to one GEMM plus n_gamma elementwise passes
(kernels that do not factor — see ``kernel_fns.KernelSpec`` — fall back to
one full evaluation per gamma).  On TPU the D² kernel computes only
upper-triangle tiles and mirrors them (``sq_dists_pallas(symmetric=True)``),
and the bf16 read path for the hinge/quantile solvers is fused into the
per-gamma epilogue's single VMEM pass (``gram_from_d2_pallas`` with
``out_dtype="bf16"``) — the Gram is never materialized in f32 at all on
that path.

Columns are task-major:  col = t * (n_lam * n_sub) + l * n_sub + s, where
"sub" is the quantile/expectile tau or the hinge class-weight index.
Folds are boolean masks (no gathers — static shapes); padding and
task-exclusion are realized as zero-width boxes, which removes a sample
from the dual EXACTLY.

liquidSVM's "warm start across the grid" appears twice:
  * across lambda/tau/w/task: solved simultaneously as GEMM columns
    (strictly stronger than sequential warm starts);
  * across gamma: the previous gamma's solution seeds the next scan step.

Selection is fused into the gamma scan (train phase and select phase in one
pass), so peak memory is O(n x columns), never O(n x whole grid x gammas).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import kernel_fns
from repro.core.grids import GridSpec
from repro.core.solvers import base as qp
from repro.core.solvers import expectile as exp_solver
from repro.core.solvers import hinge as hinge_solver
from repro.core.solvers import least_squares as ls_solver
from repro.core.solvers import quantile as q_solver

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CVConfig:
    solver: str = "hinge"           # hinge | ls | quantile | expectile
    kernel: str = "gauss_rbf"
    n_folds: int = 5
    fold_scheme: str = "random"     # random | stratified | blocks
    tol: float = 1e-3
    max_iters: int = 1000
    val_loss: str = "auto"          # auto: 0-1 for hinge, mse for ls, pinball, ...
    gram_dtype: str = "f32"         # f32 | bf16 (hinge/quantile solve reads
                                    # a 2-byte Gram, accumulates f32 — §Perf)
    keep_surface: bool = False      # retain the full validation surface
                                    # (loss + hinge FA/detection counts) per
                                    # grid point — the staged select() phase
                                    # re-runs selection rules over it without
                                    # retraining (repro.api.session)
    taus: Tuple[float, ...] = (0.5,)       # quantile/expectile levels (sub axis)
    weights: Tuple[float, ...] = (1.0,)    # hinge +1-class weight grid (sub axis)

    @property
    def n_sub(self) -> int:
        if self.solver in ("quantile", "expectile"):
            return len(self.taus)
        return len(self.weights)


class CVSelected(NamedTuple):
    """Streaming-selection output, per (task, sub)."""
    coefs: Array        # (n_folds, n, n_tasks, n_sub) fold models at the argmin
    gamma: Array        # (n_tasks, n_sub)
    lam: Array          # (n_tasks, n_sub)
    tau: Array          # (n_tasks, n_sub)
    weight: Array       # (n_tasks, n_sub)
    val_loss: Array     # (n_tasks, n_sub) best mean validation loss
    val_grid: Array     # (n_gamma, n_tasks, n_lam, n_sub) full CV surface
    fa_grid: Array      # (n_gamma, n_tasks, n_lam, n_sub) validation false-
                        # alarm COUNTS (hinge + keep_surface only, else 0)
    det_grid: Array     # (n_gamma, n_tasks, n_lam, n_sub) detection counts
    iters: Array        # (n_gamma, n_folds) box-QP iterations per solve
                        # (0 for the direct ls/expectile solves)


def make_fold_masks(
    key: Array, mask: Array, n_folds: int, scheme: str = "random", y: Array | None = None
) -> Array:
    """(n_folds, n) boolean: True = sample is in the *validation* part."""
    n = mask.shape[0]
    if scheme == "blocks":
        idx = jnp.cumsum(mask.astype(jnp.int32)) - 1
        n_valid = jnp.maximum(jnp.sum(mask.astype(jnp.int32)), 1)
        fold_of = (idx * n_folds) // n_valid
    else:
        u = jax.random.uniform(key, (n,))
        if scheme == "stratified" and y is not None:
            u = u + 10.0 * (y > 0)
        u = jnp.where(mask > 0, u, jnp.inf)
        order = jnp.argsort(u)
        rank = jnp.argsort(order)
        fold_of = rank % n_folds
    fold_of = jnp.where(mask > 0, fold_of, -1)
    return jax.nn.one_hot(fold_of, n_folds, axis=0, dtype=jnp.bool_)


def fold_train_bound(n: int, n_folds: int) -> int:
    """The most training rows a fold of an n-row padded cell can hold:
    every scheme of :func:`make_fold_masks` puts at least
    ``n_real // n_folds`` of a cell's ``n_real <= n`` real rows in each
    fold's validation part, so a fold trains on at most
    ``n - n // n_folds``."""
    return n if n_folds < 2 else n - n // n_folds


def ls_factor_side(n: int, n_folds: int) -> int:
    """Side of the block the least-squares solve factors per fold:
    :func:`fold_train_bound` rounded up to a multiple of 128, at most n.
    XLA's TPU Cholesky works in panels of 128 columns, and a side of whole
    panels leaves no ragged last panel to pad (n = 2853, 5 folds: 2304
    rather than 2283; the 2-slot wave compiles with four pads fewer and
    runs 0.6 % faster on a v5e)."""
    return min(n, -(-fold_train_bound(n, n_folds) // 128) * 128)


def grid_columns(grid: GridSpec, cfg: CVConfig, n_tasks: int):
    """Task-major flattened columns.  Returns dict of (P,) arrays + ids."""
    lam = grid.lambdas.astype(jnp.float32)
    n_lam = lam.shape[0]
    if cfg.solver in ("quantile", "expectile"):
        sub = jnp.asarray(cfg.taus, jnp.float32)
    else:
        sub = jnp.asarray(cfg.weights, jnp.float32)
    n_sub = sub.shape[0]
    lam_c = jnp.tile(jnp.repeat(lam, n_sub), n_tasks)              # (P,)
    sub_c = jnp.tile(sub, n_lam * n_tasks)                         # (P,)
    task_c = jnp.repeat(jnp.arange(n_tasks, dtype=jnp.int32), n_lam * n_sub)
    return lam_c, sub_c, task_c, n_lam, n_sub


def _val_losses(f_val: Array, y_cols: Array, val_mask_cols: Array, cfg: CVConfig,
                sub_c: Array) -> Array:
    """Masked mean validation loss per column.  All args (n, P)-shaped."""
    denom = jnp.maximum(jnp.sum(val_mask_cols, axis=0), 1.0)
    if cfg.solver == "hinge":
        if cfg.val_loss in ("auto", "zero_one"):
            losses = ((f_val * y_cols) <= 0.0).astype(jnp.float32)
        else:
            losses = jnp.maximum(0.0, 1.0 - y_cols * f_val)
    elif cfg.solver == "ls":
        losses = (y_cols - f_val) ** 2
    elif cfg.solver == "quantile":
        losses = q_solver.pinball_loss(y_cols, f_val, sub_c[None, :])
    elif cfg.solver == "expectile":
        losses = exp_solver.expectile_loss(y_cols, f_val, sub_c[None, :])
    else:
        raise ValueError(cfg.solver)
    return jnp.sum(losses * val_mask_cols, axis=0) / denom


def _solve_columns(k_full, y_cols, train_cols, lam_c, sub_c, n_eff_cols, cfg, c0, l_est):
    """train_cols (n, P): 1 = sample is in this column's training set.

    Returns ``(c, iters)`` — iters is the box-QP iteration count (0 for the
    direct ls/expectile solves), surfaced so callers can assert that warm
    starts actually shorten the solve.
    """
    if cfg.solver in ("hinge", "quantile"):
        cost = 1.0 / (2.0 * lam_c[None, :] * jnp.maximum(n_eff_cols[None, :], 1.0))
        if cfg.solver == "hinge":
            w = jnp.where(y_cols > 0, sub_c[None, :], 1.0)  # class weight on +1
            edge = y_cols * cost * w * train_cols
            lo, hi = jnp.minimum(0.0, edge), jnp.maximum(0.0, edge)
        else:
            lo = cost * (sub_c[None, :] - 1.0) * train_cols
            hi = cost * sub_c[None, :] * train_cols
        res = qp.box_qp(k_full, y_cols * train_cols, lo, hi, c0=c0,
                        tol=cfg.tol, max_iters=cfg.max_iters, l_est=l_est)
        return res.c, res.iters
    if cfg.solver == "ls":
        # all columns must share the fold train mask (task_mask == 1)
        c = ls_solver.solve_columns(
            k_full, y_cols, lam_c, n_eff_cols, train_cols[:, 0],
            ls_factor_side(k_full.shape[0], cfg.n_folds))
        return c, jnp.int32(0)
    if cfg.solver == "expectile":
        tm = train_cols[:, 0]
        n_eff = n_eff_cols[0]
        c = exp_solver.solve_expectile(
            k_full, y_cols[:, 0], sub_c, lam_c, n_eff, train_mask=tm, c0=c0)
        return c, jnp.int32(0)
    raise ValueError(cfg.solver)


@functools.partial(jax.jit, static_argnames=("cfg", "n_lam", "n_sub"))
def cv_cell(
    x: Array,              # (n, d) padded cell
    y_tasks: Array,        # (n_tasks, n) labels/targets (0 where excluded)
    task_mask: Array,      # (n_tasks, n) 1 = sample participates in task
    mask: Array,           # (n,) 1 = real sample
    gammas: Array,         # (n_gamma,)
    lam_c: Array, sub_c: Array, task_c: Array,   # (P,) task-major columns
    fold_key: Array,
    cfg: CVConfig,
    n_lam: int,
    n_sub: int,
) -> CVSelected:
    """Fused train+select CV over one working set, all tasks at once."""
    n = x.shape[0]
    n_tasks = y_tasks.shape[0]
    p = lam_c.shape[0]

    y_strat = y_tasks[0] if cfg.solver == "hinge" else None
    val_folds = make_fold_masks(fold_key, mask, cfg.n_folds, cfg.fold_scheme, y_strat)
    train_folds = (~val_folds) & (mask > 0)[None, :]          # (k, n)

    y_cols = y_tasks[task_c].T                                 # (n, P)
    colmask = task_mask[task_c].T * mask[:, None]              # (n, P)

    spec = kernel_fns.get_spec(cfg.kernel)
    use_d2 = spec.factors_through_d2
    want_bf16 = cfg.gram_dtype == "bf16" and cfg.solver in ("hinge", "quantile")
    gram_dtype = "bf16" if want_bf16 else "f32"
    track_rates = cfg.keep_surface and cfg.solver == "hinge"
    # ONE D² for the whole gamma scan: the O(n²d) MXU cross term is hoisted
    # out of the lax.scan; each scan step replays only the O(n²) epilogue.
    # named_scope markers label the D²-vs-epilogue-vs-solve split in the
    # compiled program's HLO metadata; the trace's op events carry no
    # scope, so ``obs.jaxprof.scope_tables`` joins them on instruction name.
    if use_d2:
        with jax.named_scope("cv.d2"):
            cg = kernel_fns.CachedGram.build(x, name=cfg.kernel)
    else:
        cg = None

    def per_gamma(carry, gamma):
        best_val, best_cfs, best_g, best_l, c0_all = carry
        with jax.named_scope("cv.epilogue"):
            if use_d2:
                k_full = cg.gram(gamma, gram_dtype)            # VPU-only pass
            else:
                k_full = spec.fn(x, x, gamma)                  # ONE Gram/gamma
                if want_bf16:
                    k_full = k_full.astype(jnp.bfloat16)  # 2-byte solver reads

        # ONE Lipschitz estimate per gamma, shared by every fold: for a PSD
        # Gram, lambda_max(M K M) <= lambda_max(K) for any 0/1 mask M, so
        # the shared step 1/L is valid for all masked subproblems, and no
        # fold builds its own masked Gram.
        l_est = (qp.power_iteration_l(k_full)
                 if cfg.solver in ("hinge", "quantile") else None)

        def per_fold(tr_mask, va_mask, c0_f):
            tr_cols = tr_mask.astype(jnp.float32)[:, None] * colmask   # (n, P)
            va_cols = va_mask.astype(jnp.float32)[:, None] * colmask
            n_eff_cols = jnp.sum(tr_cols, axis=0)                      # (P,)
            coefs, iters = _solve_columns(k_full, y_cols, tr_cols, lam_c,
                                          sub_c, n_eff_cols, cfg, c0_f, l_est)
            f_val = jnp.matmul(k_full, coefs,
                               precision=jax.lax.Precision.HIGHEST)
            vl = _val_losses(f_val, y_cols, va_cols, cfg, sub_c)
            if track_rates:
                # validation-fold confusion counts per column: every valid
                # sample sits in exactly ONE validation fold, so summing the
                # per-fold counts gives exact whole-set validation rates —
                # the NP/ROC selection rules read these, never the train set
                pred_pos = (f_val > 0) & (va_cols > 0)
                fa = jnp.sum((pred_pos & (y_cols < 0)).astype(jnp.float32), 0)
                det = jnp.sum((pred_pos & (y_cols > 0)).astype(jnp.float32), 0)
            else:
                fa = det = jnp.zeros_like(vl)
            return vl, fa, det, coefs, iters

        with jax.named_scope("cv.solve"):
            vl, fa, det, coefs, iters = jax.vmap(per_fold)(
                train_folds, val_folds, c0_all)
        vl_mean = jnp.mean(vl, axis=0)                                  # (P,)
        fa_tls = jnp.sum(fa, axis=0).reshape(n_tasks, n_lam, n_sub)
        det_tls = jnp.sum(det, axis=0).reshape(n_tasks, n_lam, n_sub)

        # streaming selection: best lambda for this gamma, per (task, sub)
        vl_tls = vl_mean.reshape(n_tasks, n_lam, n_sub)
        lam_star = jnp.argmin(vl_tls, axis=1)                           # (T, S)
        val_star = jnp.min(vl_tls, axis=1)                              # (T, S)
        t_idx = jnp.arange(n_tasks)[:, None]
        s_idx = jnp.arange(n_sub)[None, :]
        flat_cols = (t_idx * n_lam + lam_star) * n_sub + s_idx          # (T, S)
        cand_cfs = coefs[:, :, flat_cols]                               # (k, n, T, S)
        improved = val_star < best_val                                   # (T, S)
        best_val = jnp.where(improved, val_star, best_val)
        best_cfs = jnp.where(improved[None, None], cand_cfs, best_cfs)
        best_g = jnp.where(improved, gamma, best_g)
        best_l = jnp.where(improved, lam_c[flat_cols.reshape(-1)].reshape(n_tasks, n_sub), best_l)
        carry = (best_val, best_cfs, best_g, best_l, coefs)             # warm start
        return carry, (vl_tls, fa_tls, det_tls, iters)

    init = (
        jnp.full((n_tasks, n_sub), jnp.inf, jnp.float32),
        jnp.zeros((cfg.n_folds, n, n_tasks, n_sub), jnp.float32),
        jnp.zeros((n_tasks, n_sub), jnp.float32),
        jnp.zeros((n_tasks, n_sub), jnp.float32),
        jnp.zeros((cfg.n_folds, n, p), jnp.float32),
    )
    (best_val, best_cfs, best_g, best_l, _), (vl_all, fa_all, det_all,
                                              iters_all) = \
        jax.lax.scan(per_gamma, init, gammas)

    sub_grid = sub_c[:n_sub]
    if cfg.solver in ("quantile", "expectile"):
        tau = jnp.broadcast_to(sub_grid[None, :], (n_tasks, n_sub))
        weight = jnp.ones((n_tasks, n_sub), jnp.float32)
    else:
        tau = jnp.full((n_tasks, n_sub), 0.5, jnp.float32)
        weight = jnp.broadcast_to(sub_grid[None, :], (n_tasks, n_sub))

    return CVSelected(coefs=best_cfs, gamma=best_g, lam=best_l, tau=tau,
                      weight=weight, val_loss=best_val, val_grid=vl_all,
                      fa_grid=fa_all, det_grid=det_all,
                      iters=iters_all.astype(jnp.int32))


def _solve_columns_at_core(x, y_tasks, task_mask, mask, gamma, lam_cols,
                           sub_cols, task_cols, fold_key, c0, cfg):
    """Unjitted body shared by :func:`solve_columns_at` (one cell) and
    :func:`solve_columns_batched` (a vmapped group of cells)."""
    y_strat = y_tasks[0] if cfg.solver == "hinge" else None
    val_folds = make_fold_masks(fold_key, mask, cfg.n_folds, cfg.fold_scheme,
                                y_strat)
    train_folds = (~val_folds) & (mask > 0)[None, :]          # (k, n)
    y_cols = y_tasks[task_cols].T                              # (n, P')
    colmask = task_mask[task_cols].T * mask[:, None]           # (n, P')

    spec = kernel_fns.get_spec(cfg.kernel)
    k_full = spec.fn(x, x, gamma)
    if cfg.gram_dtype == "bf16" and cfg.solver in ("hinge", "quantile"):
        k_full = k_full.astype(jnp.bfloat16)
    l_est = (qp.power_iteration_l(k_full)
             if cfg.solver in ("hinge", "quantile") else None)
    n, p_cols = x.shape[0], lam_cols.shape[0]
    if c0 is None:
        c0 = jnp.zeros((cfg.n_folds, n, p_cols), jnp.float32)
    elif c0.ndim == 2:
        # one shared start (nearest cached grid column, solved at a possibly
        # different (gamma, lambda)) broadcast to every fold — _solve_columns
        # clips it into each column's box (qp.clip_warm_start) first.
        c0 = jnp.broadcast_to(c0.astype(jnp.float32)[None],
                              (cfg.n_folds, n, p_cols))
    else:
        # per-fold starts: each fold resumes from ITS OWN cached solution
        # (the fold coefs this function returns) — the re-materialization
        # path, where the start is already at the optimum.
        c0 = c0.astype(jnp.float32)

    def per_fold(tr_mask, c0_f):
        tr_cols = tr_mask.astype(jnp.float32)[:, None] * colmask
        n_eff_cols = jnp.sum(tr_cols, axis=0)
        return _solve_columns(k_full, y_cols, tr_cols, lam_cols, sub_cols,
                              n_eff_cols, cfg, c0_f, l_est)

    coefs, iters = jax.vmap(per_fold)(train_folds, c0)         # (folds, n, P')
    return jnp.mean(coefs, axis=0), jnp.sum(iters), coefs


@functools.partial(jax.jit, static_argnames=("cfg",))
def solve_columns_at(
    x: Array,              # (n, d) padded cell
    y_tasks: Array,        # (n_tasks, n)
    task_mask: Array,      # (n_tasks, n)
    mask: Array,           # (n,)
    gamma: Array,          # scalar — ONE gamma for every requested column
    lam_cols: Array,       # (P',) per-column lambda VALUES
    sub_cols: Array,       # (P',) per-column tau / class weight
    task_cols: Array,      # (P',) per-column task index
    fold_key: Array,
    cfg: CVConfig,
    c0: Array | None = None,   # (n, P') shared or (folds, n, P') per-fold
) -> tuple[Array, Array, Array]:
    """Targeted re-solve: the given columns at one gamma, all folds, fold-
    averaged — the select() phase's "one targeted wave".

    Changing the selection rule over a retained surface only moves a handful
    of (task, sub) winners to new (gamma, lambda) coordinates; this solves
    exactly those columns (one Gram, one batched box-QP per distinct gamma)
    instead of re-running the full fold x grid sweep.  ``fold_key`` must be
    the cell's training key so the CV folds — and hence the model the
    surface scored — are reproduced exactly.

    ``c0`` warm-starts the solve, box-clipped per column (warm or cold
    ``c0=None`` converges to the same box-QP optimum within ``cfg.tol``):

    * ``(n, P')`` — one start shared by every fold, e.g. the nearest
      cached grid column from ``TrainResult``.  Measured effect on the
      batched FISTA iteration count: roughly neutral — FISTA's count is
      gated by the worst-conditioned column, and a neighbor-grid start is
      far from that column's optimum.  Kept because clipping makes it
      free and never worse than cold.
    * ``(folds, n, P')`` — per-fold starts.  When these are the fold
      coefs of a previous solve of the SAME columns (the third return
      value), each fold starts at its own optimum and the re-solve
      collapses to a KKT check — orders of magnitude fewer iterations
      (asserted in ``tests/test_staged_api.py``).  This is the
      re-materialization path: rebuilding a model the surface already
      scored without paying the solve again.

    Returns ``(fold-mean coefs (n, P'), total box-QP iters,
    per-fold coefs (folds, n, P'))``.
    """
    return _solve_columns_at_core(x, y_tasks, task_mask, mask, gamma,
                                  lam_cols, sub_cols, task_cols, fold_key,
                                  c0, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def solve_columns_batched(
    x: Array,              # (C, n, d) stacked cells
    y_tasks: Array,        # (C, n_tasks, n)
    task_mask: Array,      # (C, n_tasks, n)
    mask: Array,           # (C, n)
    gamma: Array,          # (C,) one gamma per cell (same grid index)
    lam_cols: Array,       # (C, P') per-column lambda values
    sub_cols: Array,       # (C, P')
    task_cols: Array,      # (C, P')
    fold_key: Array,       # (C, 2)
    c0: Array,             # (C, n, P') shared or (C, folds, n, P') per-fold
    cfg: CVConfig,
) -> tuple[Array, Array, Array]:
    """Vmapped :func:`solve_columns_at`: ONE launch for every moved cell
    that shares a gamma-grid index, instead of one jit call per (cell,
    gamma).  Returns ``(coefs (C, n, P'), iters (C,),
    fold_coefs (C, folds, n, P'))``.
    """
    core = functools.partial(_solve_columns_at_core, cfg=cfg)
    return jax.vmap(core)(x, y_tasks, task_mask, mask, gamma, lam_cols,
                          sub_cols, task_cols, fold_key, c0)
