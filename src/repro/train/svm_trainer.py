"""The full liquidSVM application cycle: train -> select -> test.

The staged machinery lives in :mod:`repro.api.session` (``SVM`` sessions
producing persistable ``TrainResult`` / ``SelectResult`` / ``TestResult``
stage artifacts, mirroring the package's ``svm-train`` / ``svm-select`` /
``svm-test`` binaries); scenario front-ends (``mcSVM``, ``qtSVM``,
``nplSVM``, ``rocSVM``, ...) and the string-key config layer live in
:mod:`repro.api`; ``python -m repro.cli`` drives the stages as separate
processes.  This module keeps the estimator-style entry point:

:class:`LiquidSVM` is now a thin shim — ``fit()`` is exactly
``SVM.train()`` followed by ``select()`` (the CV-loss argmin rule, or the
validation-surface Neyman-Pearson rule for ``scenario="npsvm"``), and the
test-phase methods delegate to the resulting ``SelectResult``.  Everything
``fit`` used to expose (``coefs``, ``gamma``, ``plan``, ``np_fa``, ...)
is still populated, and under the argmin rule the decisions are
bitwise-identical to the old fused implementation (selection reuses the
exact streaming-argmin models the train stage cached).

Ingestion is streaming end-to-end: ``fit`` takes an in-memory array OR any
``repro.pipeline`` chunk source (memmap ``.npy`` path, npz shard list,
custom ``ChunkSource``); the transient footprint of a fit is
O(wave · cell).  ``n_slots_per_wave`` bounds how many packed cell slots
are staged and solved per launch; ``ckpt_dir`` makes the wave loop
resumable (see ``distributed.cell_trainer.train_cells_waves``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np
from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class SVMTrainerConfig:
    scenario: str = "binary"        # binary | ova | ava | weighted | npsvm |
                                    # quantile | expectile | ls
    solver: str = "auto"            # auto: hinge for classification, else ls/quantile/expectile
    kernel: str = "gauss_rbf"
    cell_method: str = "none"       # none | random | voronoi | overlap | recursive | coarse_fine
    cell_size: int = 2000
    n_folds: int = 5
    fold_scheme: str = "random"
    grid_choice: int = 0
    adaptivity_control: int = 0
    taus: Tuple[float, ...] = (0.05, 0.5, 0.95)
    weights: Tuple[float, ...] = (1.0,)
    np_alpha: float = 0.05          # npsvm: false-alarm budget on class -1
    tol: float = 1e-3
    max_iters: int = 1000
    seed: int = 0
    scale: bool = True              # train-statistics feature scaling
    n_slots_per_wave: Optional[int] = None   # None: all slots in one wave
    chunk_size: int = 65536                  # streaming chunk rows

    def resolve_solver(self) -> str:
        if self.solver != "auto":
            return self.solver
        return {"binary": "hinge", "ova": "hinge", "ava": "hinge",
                "weighted": "hinge", "npsvm": "hinge", "quantile": "quantile",
                "expectile": "expectile", "ls": "ls"}[self.scenario]


class LiquidSVM:
    """Fused-cycle estimator (back-compat shim over the staged API).

    .. deprecated::
        ``LiquidSVM.fit`` now runs ``repro.api.SVM.train()`` +
        ``select()`` internally; prefer the staged session API
        (:mod:`repro.api`) — it keeps the train artifact so selection can
        be re-run under a different rule (NPL constraints, ROC fronts)
        without retraining, and each stage can persist/reload across
        processes (``python -m repro.cli``).
    """

    def __init__(self, config: SVMTrainerConfig = SVMTrainerConfig(),
                 mesh: Optional[Mesh] = None,
                 mesh_axes: Optional[Tuple[str, ...]] = None):
        self.config = config
        self.mesh = mesh
        self.mesh_axes = mesh_axes
        self._fitted = False

    # ------------------------------------------------------------- train
    def fit(self, x, y: np.ndarray,
            ckpt_dir: Optional[str] = None) -> "LiquidSVM":
        """Fit from an (n, d) array or any chunk source (see module doc).

        Equivalent to ``SVM.train()`` + ``select("argmin")`` (scenario
        ``npsvm``: the ``"npl"`` rule, whose false-alarm/detection rates
        come from the retained VALIDATION surface, not the train set).
        ``ckpt_dir``: per-wave checkpointing/resume of the cell solves.
        """
        from repro.api.session import SVM

        cfg = self.config
        sess = SVM(x, y, config=cfg, mesh=self.mesh,
                   mesh_axes=self.mesh_axes)
        tr = sess.train(ckpt_dir=ckpt_dir)
        rule = "npl" if cfg.scenario == "npsvm" else "argmin"
        sel = sess.select(rule)
        self.session, self.train_result, self.select_result = sess, tr, sel

        # legacy attribute surface (everything the fused fit used to set)
        self.scaler, self.tasks = tr.scaler, tr.tasks
        self.plan, self.packed, self.cv_cfg = tr.plan, tr.packed, tr.cv_cfg
        self.x_cells, self.mask_cells = tr.x_cells, tr.mask_cells
        self.coefs, self.gamma = sel.coefs, sel.gamma
        self.lam, self.tau = sel.lam, sel.tau
        self.val_loss = sel.val_loss
        if cfg.scenario == "npsvm":
            self.np_fa = np.asarray(sel.extras["np_fa"])[0]
            self.np_det = np.asarray(sel.extras["np_det"])[0]
            self.np_weight_idx = sel.default_sub
        self._fitted = True
        return self

    # ------------------------------------------------------------- serving
    def to_bank(self, drop_tol: float | None = 0.0, dtype: str = "f32",
                dedup: bool = True):
        """Compact the fitted cell models into a serving ModelBank."""
        assert self._fitted
        return self.select_result.to_bank(drop_tol=drop_tol, dtype=dtype,
                                          dedup=dedup)

    # ------------------------------------------------------------- test
    def decision_function(self, x_test: np.ndarray) -> np.ndarray:
        """(m, d) -> (m, T, S) via Voronoi routing to owning cells."""
        assert self._fitted
        return self.select_result.decision_function(x_test)

    def predict(self, x_test: np.ndarray) -> np.ndarray:
        assert self._fitted
        return self.select_result.predict(x_test)

    def error(self, x_test: np.ndarray, y_test: np.ndarray) -> float:
        assert self._fitted
        return float(self.select_result.test(x_test, y_test).error)
