"""High-level single-working-set SVM: the paper's train / select / test cycle
for one (possibly multi-task) working set.  Cell composition lives in
``repro.cells`` / ``repro.train.svm_trainer``; distribution in
``repro.distributed``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cv as cv_mod
from repro.core import grids, kernel_fns, select
from repro.tasks.builder import combine_decisions

Array = jax.Array


class TrainedSVM(NamedTuple):
    """Everything the test phase needs (a pytree — shards/checkpoints cleanly).

    Multi-task: coefs (n, n_tasks, n_sub); per-(task, sub) hyper-params.
    """
    sv_x: Array        # (n, d)
    sv_mask: Array     # (n,)
    coefs: Array       # (n, n_tasks, n_sub)
    gamma: Array       # (n_tasks, n_sub)
    lam: Array
    tau: Array
    val_loss: Array
    kernel: str = "gauss_rbf"

    def decision_function(self, x_test: Array) -> Array:
        """(m, d) -> (m, n_tasks, n_sub).

        Each (task, sub) can select a different gamma; the cross D² matrix
        is gamma-independent, so it is computed once and each (task, sub)
        replays only the cheap per-gamma epilogue (vmap over the small task
        axis).  Kernels without a D² factorization fall back to one full
        cross-Gram per (task, sub).
        """
        x_test = jnp.asarray(x_test, jnp.float32)
        gram_of = kernel_fns.cross_gram_fn(x_test, self.sv_x, self.kernel)

        def per_ts(gamma, coef):
            return jnp.matmul(gram_of(gamma), coef,
                              precision=jax.lax.Precision.HIGHEST)

        t, s = self.gamma.shape
        gflat = self.gamma.reshape(-1)
        cflat = self.coefs.reshape(self.coefs.shape[0], -1).T  # (T*S, n)
        out = jax.vmap(per_ts)(gflat, cflat)                   # (T*S, m)
        return out.T.reshape(x_test.shape[0], t, s)

    def predict_label(self, x_test: Array, scenario: str = "binary",
                      classes: np.ndarray | None = None,
                      pairs: np.ndarray | None = None,
                      sub: int = 0) -> np.ndarray:
        """Scenario-aware labels: binary signs by default; OvA argmax /
        AvA pairwise votes over the task axis when a multi-task model is
        paired with its class values (``tasks.builder`` combiners), so
        multi-class models predict class values end-to-end."""
        return combine_decisions(self.decision_function(x_test), scenario,
                                 classes=classes, pairs=pairs, sub=sub)


def train_select(
    x: Array,
    y: Array,
    mask: Array | None = None,
    cfg: cv_mod.CVConfig = cv_mod.CVConfig(),
    grid: grids.GridSpec | None = None,
    y_tasks: Array | None = None,
    task_mask: Array | None = None,
    seed: int = 0,
) -> TrainedSVM:
    """Train + select on one working set.

    Single-task by default (y used directly); pass y_tasks/task_mask
    (n_tasks, n) for OvA/AvA multi-task working sets.
    """
    x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    mask = jnp.ones((n,), jnp.float32) if mask is None else jnp.asarray(mask, jnp.float32)
    if y_tasks is None:
        y_tasks = jnp.asarray(y, jnp.float32)[None, :]
        task_mask = jnp.ones_like(y_tasks)
    else:
        y_tasks = jnp.asarray(y_tasks, jnp.float32)
        task_mask = (jnp.ones_like(y_tasks) if task_mask is None
                     else jnp.asarray(task_mask, jnp.float32))

    if grid is None:
        med = kernel_fns.median_heuristic(x, mask)
        grid = grids.liquid_grid(n=int(n), dim=int(d), median_dist=med)

    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(grid, cfg, y_tasks.shape[0])
    key = jax.random.PRNGKey(seed)
    sel = cv_mod.cv_cell(x, y_tasks, task_mask, mask, grid.gammas,
                         lam_c, sub_c, task_c, key, cfg, n_lam=n_lam, n_sub=n_sub)
    combined = select.combine_fold_models(sel.coefs)   # (n, T, S)
    return TrainedSVM(sv_x=x, sv_mask=mask, coefs=combined,
                      gamma=sel.gamma, lam=sel.lam, tau=sel.tau,
                      val_loss=sel.val_loss, kernel=cfg.kernel)


def test_error(model: TrainedSVM, x_test: Array, y_test: Array,
               task: str = "classify",
               classes: np.ndarray | None = None,
               pairs: np.ndarray | None = None,
               sub: int = 0) -> Array:
    """Test-phase error.  ``task`` "classify"/"mse" evaluate the (0, sub)
    decision column (single-task models); "ova"/"ava" combine the full task
    axis into class values first (misclassification rate vs y_test)."""
    if task in ("ova", "ava"):
        pred = model.predict_label(jnp.asarray(x_test, jnp.float32),
                                   scenario=task, classes=classes,
                                   pairs=pairs, sub=sub)
        return jnp.mean((jnp.asarray(pred) != jnp.asarray(y_test))
                        .astype(jnp.float32))
    f = model.decision_function(jnp.asarray(x_test, jnp.float32))[:, 0, sub]
    y_test = jnp.asarray(y_test, jnp.float32)
    if task == "classify":
        return jnp.mean((f * y_test <= 0).astype(jnp.float32))
    if task == "mse":
        return jnp.mean((f - y_test) ** 2)
    raise ValueError(task)
