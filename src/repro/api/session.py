"""Staged train -> select -> test sessions (liquidSVM's three-binary cycle).

liquidSVM exposes its application cycle as three separable stages —
``svm-train`` solves the full fold x grid, ``svm-select`` picks
hyper-parameters (re-runnable with different criteria: NPL constraints,
ROC weight fronts) WITHOUT retraining, ``svm-test`` evaluates — and every
binding, from the R front-ends to the command line, composes them.  This
module is that cycle for the JAX port:

    sess = SVM(x, y, config)            # or a repro.api front-end
    tr   = sess.train()                 # TrainResult: models + CV surface
    sel  = sess.select("npl", alpha=.05)   # SelectResult: one targeted wave
    res  = sess.test(x_test, y_test)    # TestResult: streamed errors

Stage artifacts are first-class and persistable (``save``/``load`` through
``repro.train.checkpoint`` step dirs), so the stages can run as separate
processes — exactly what ``python -m repro.cli {train,select,test}`` does —
and a predict server cold-starts from the select output alone
(``SelectResult.to_bank()`` -> ``repro.serve.SVMEngine``).

Why re-selection is cheap: ``train()`` retains the per-cell validation-loss
surface over the whole (gamma, task, lambda, sub) grid plus — for hinge —
validation false-alarm/detection COUNTS (``CVConfig.keep_surface``; the
surface is O(slots x grid), tiny next to the coefficients).  ``select``
applies a registered :mod:`repro.core.select` rule over the surface and
re-solves ONLY the (task, sub) columns whose winning grid coordinates
moved off the train-time argmin (those models are already cached): one
targeted ``solve_columns_at`` wave per (cell, new gamma), not a refit.
Under the "argmin" rule nothing is re-solved at all, so
``train() -> select("argmin") -> test()`` is bitwise-identical to the old
fused ``LiquidSVM.fit``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import obs
from repro.cells.builder import CellPlan
from repro.core import cv as cv_mod
from repro.core import grids, kernel_fns
from repro.core import select as select_mod
from repro.data.scaling import Scaler
from repro.distributed.cell_trainer import predict_cells, train_cells_waves
from repro.distributed.planner import PackedCells, group_rows, pack_cells
from repro.pipeline.cell_stream import build_cells_stream
from repro.pipeline.dataset import ArraySource, ChunkSource, ScaledSource, as_source
from repro.tasks.builder import TaskSet, combine_decisions, make_tasks
from repro.train import checkpoint as ckpt_mod
from repro.train.svm_trainer import SVMTrainerConfig

_TRAIN_FORMAT = "svm_train_result_v1"
_SELECT_FORMAT = "svm_select_result_v1"

# scenario -> the selection rule its select() stage defaults to
_DEFAULT_RULES = {"npsvm": "npl", "quantile": "quantile",
                  "expectile": "expectile"}


# ----------------------------------------------------------- serialization
def _cfg_to_json(cfg) -> dict:
    return dataclasses.asdict(cfg)


def _cfg_from_json(cls, d: dict):
    kw = dict(d)
    for k in ("taus", "weights"):
        if kw.get(k) is not None:
            kw[k] = tuple(kw[k])
    return cls(**kw)


def _ctx_tree(plan: CellPlan, packed: PackedCells, scaler: Scaler,
              tasks: TaskSet) -> Dict[str, np.ndarray]:
    """The shared stage context (routing + scaling + tasks) as a flat tree."""
    # index arrays stored int32 (the restore path runs under 32-bit jax;
    # int64 leaves would be silently truncated) and widened on load
    return {
        "plan_indices": plan.indices, "plan_mask": plan.mask,
        "plan_owner": np.asarray(plan.owner, np.int32),
        "plan_centers": plan.centers,
        "plan_coarse_of": plan.coarse_of,
        "packed_order": np.asarray(packed.order, np.int32),
        "packed_slot_of_cell": np.asarray(packed.slot_of_cell, np.int32),
        "scaler_mean": np.asarray(scaler.mean),
        "scaler_std": np.asarray(scaler.std),
        "tasks_labels": tasks.labels, "tasks_task_mask": tasks.task_mask,
        "tasks_classes": np.asarray(tasks.classes, np.float32),
        "tasks_pairs": np.asarray(tasks.pairs, np.int32),
        "tasks_taus": np.asarray(tasks.taus, np.float32),
        "tasks_weights": np.asarray(tasks.weights, np.float32),
    }


def _ctx_from_tree(t: Dict[str, np.ndarray], extra: dict):
    plan = CellPlan(indices=t["plan_indices"], mask=t["plan_mask"],
                    owner=np.asarray(t["plan_owner"], np.int32),
                    centers=t["plan_centers"],
                    coarse_of=t["plan_coarse_of"])
    packed = PackedCells(order=np.asarray(t["packed_order"], np.int64),
                         slot_of_cell=np.asarray(t["packed_slot_of_cell"],
                                                 np.int64),
                         n_devices=int(extra["packed_n_devices"]),
                         slots_per_device=int(extra["packed_slots_per_device"]))
    scaler = Scaler(mean=t["scaler_mean"], std=t["scaler_std"])
    tasks = TaskSet(kind=extra["tasks_kind"], labels=t["tasks_labels"],
                    task_mask=t["tasks_task_mask"], classes=t["tasks_classes"],
                    pairs=t["tasks_pairs"], taus=t["tasks_taus"],
                    weights=t["tasks_weights"])
    return plan, packed, scaler, tasks


def _ctx_extra(config, cv_cfg, tasks: TaskSet, packed: PackedCells) -> dict:
    return {"config": _cfg_to_json(config), "cv_cfg": _cfg_to_json(cv_cfg),
            "tasks_kind": tasks.kind, "packed_n_devices": packed.n_devices,
            "packed_slots_per_device": packed.slots_per_device}


def _load_tree(ckpt_dir: str, want_format: str):
    if ckpt_mod.peek_manifest(ckpt_dir)["extra"].get("format") != want_format:
        got = ckpt_mod.peek_manifest(ckpt_dir)["extra"].get("format")
        raise ValueError(f"{ckpt_dir} is not a {want_format} checkpoint "
                         f"(format={got!r})")
    return ckpt_mod.restore_self_describing(ckpt_dir)


# ----------------------------------------------------------------- results
@dataclasses.dataclass
class TestResult:
    """Streamed test-stage output."""
    error: float              # scenario error (0-1 loss / pinball / mse ...)
    n: int                    # rows evaluated
    details: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class TrainResult:
    """Everything ``svm-train`` produced: cell models at the CV-loss argmin
    PLUS the retained validation surface and the staged cell data needed to
    re-solve a handful of columns when a different rule picks different
    winners.  ``select(rule)`` is re-runnable; ``save``/``load`` make the
    stage a process boundary."""
    config: SVMTrainerConfig
    cv_cfg: cv_mod.CVConfig
    scaler: Scaler
    plan: CellPlan
    packed: PackedCells
    tasks: TaskSet
    lambdas: np.ndarray        # (L,) shared lambda grid values
    gammas_cells: np.ndarray   # (slots, G) per-cell adaptive gamma grids
    fold_keys: np.ndarray      # (slots, 2) per-cell fold PRNG keys
    x_cells: np.ndarray        # (slots, k, d) staged (scaled) cell rows
    mask_cells: np.ndarray     # (slots, k)
    y_cells: np.ndarray        # (slots, T, k) task labels per cell
    tmask_cells: np.ndarray    # (slots, T, k)
    coefs: np.ndarray          # (slots, k, T, S) argmin fold-averaged models
    gamma: np.ndarray          # (slots, T, S) argmin winners
    lam: np.ndarray
    tau: np.ndarray
    val_loss: np.ndarray
    surf_loss: np.ndarray      # (slots, G, T, L, S)
    surf_fa: np.ndarray        # (slots, G, T, L, S) validation FA counts
    surf_det: np.ndarray
    n: int
    d: int

    # ---------------------------------------------------------- surface
    def class_counts(self) -> Tuple[np.ndarray, np.ndarray]:
        """(neg, pos) valid-sample totals per (slot, task) — the exact
        denominators for the retained count grids."""
        on = (self.tmask_cells > 0) & (self.mask_cells[:, None, :] > 0)
        neg = ((self.y_cells < 0) & on).sum(-1).astype(np.float32)
        pos = ((self.y_cells > 0) & on).sum(-1).astype(np.float32)
        return neg, pos

    def surface(self) -> select_mod.Surface:
        neg, pos = self.class_counts()
        return select_mod.Surface(loss=self.surf_loss, fa=self.surf_fa,
                                  det=self.surf_det, neg=neg, pos=pos,
                                  gammas=self.gammas_cells,
                                  lambdas=self.lambdas)

    # ----------------------------------------------------------- select
    def select(self, rule: Optional[str] = None,
               mesh: Optional[Mesh] = None,
               mesh_axes: Optional[Tuple[str, ...]] = None,
               **rule_kwargs) -> "SelectResult":
        """Apply a selection rule over the retained surface.

        Columns whose winning (gamma, lambda) equals the train-time argmin
        reuse the cached models untouched (bitwise); the rest are re-solved
        by :func:`repro.core.cv.solve_columns_batched` — ALL moved cells
        sharing a winning gamma-grid index go into one vmapped launch
        (columns padded to one static width so repeated re-selections share
        one compiled program), each warm-started from its cell's cached
        argmin model instead of ``c0 = 0``.  ``stats`` reports how little
        was solved versus the full sweep (``resolve_calls`` counts
        launches, ``solver_iters`` total box-QP iterations).
        """
        cfg = self.config
        rule = rule or _DEFAULT_RULES.get(cfg.scenario, "argmin")
        if rule in ("npl", "roc") and self.cv_cfg.solver != "hinge":
            raise ValueError(f"rule {rule!r} needs the hinge solver "
                             f"(validation FA/detection counts); "
                             f"got {self.cv_cfg.solver!r}")
        ctx = select_mod.SelectContext(
            scenario=cfg.scenario,
            weights=np.asarray(cfg.weights, np.float32),
            taus=np.asarray(cfg.taus, np.float32),
            alpha=float(rule_kwargs.pop("alpha", cfg.np_alpha)),
            npl_class=int(rule_kwargs.pop("npl_class", -1)))
        if rule_kwargs:
            raise TypeError(f"unknown select() options {sorted(rule_kwargs)}")
        surface = self.surface()
        res = select_mod.get_rule(rule)(surface, ctx)

        base_g, base_l = select_mod.argmin_winners(self.surf_loss)
        nonempty = self.mask_cells.sum(-1) > 0                 # (slots,)
        need = ((res.g_idx != base_g) | (res.l_idx != base_l)) \
            & nonempty[:, None, None]                          # (slots, T, S)

        coefs = self.coefs.copy()
        gamma, lam = self.gamma.copy(), self.lam.copy()
        val = self.val_loss.copy()
        n_tasks, n_sub = gamma.shape[1], gamma.shape[2]
        n_cols = n_tasks * n_sub
        if self.cv_cfg.solver in ("quantile", "expectile"):
            sub_grid = np.asarray(cfg.taus, np.float32)
        else:
            sub_grid = np.asarray(cfg.weights, np.float32)
        stats = {"rule": rule, "grid_columns": surface.grid_columns,
                 "winners_moved": int(need.sum()),
                 "columns_resolved": 0, "resolve_calls": 0,
                 "solver_iters": 0}

        m_resolved = obs.metrics.counter("select.columns_resolved")
        # group moved cells by winning gamma-grid INDEX: every cell in a
        # group re-solves in ONE vmapped launch, not one jit call per
        # (cell, gamma)
        groups: Dict[int, list] = {}
        for c in np.flatnonzero(need.any(axis=(1, 2))):
            for g in np.unique(res.g_idx[c][need[c]]):
                groups.setdefault(int(g), []).append(int(c))
        for g, cells in sorted(groups.items()):
            ts_of, pads = {}, {}
            lam_b, sub_b, task_b, c0_b = [], [], [], []
            for c in cells:
                ts = np.argwhere(need[c] & (res.g_idx[c] == g))  # (m, 2)
                ts_of[c] = ts
                # pad to the static (T*S) width: one compiled shape for
                # every re-selection of this fit
                pad = np.concatenate(
                    [ts, np.repeat(ts[:1], n_cols - len(ts), axis=0)])
                pads[c] = pad
                lam_b.append(self.lambdas[res.l_idx[c, pad[:, 0],
                                                    pad[:, 1]]])
                sub_b.append(sub_grid[pad[:, 1]])
                task_b.append(pad[:, 0])
                # warm start: the cached argmin model of the SAME (task,
                # sub) column — the nearest solved grid column; box-clipped
                # to the new (lambda, weight) box inside the solver
                c0_b.append(self.coefs[c][:, pad[:, 0], pad[:, 1]])
            with obs.tracer.span("select.resolve") as sp:
                sp.set(gamma_idx=int(g), cells=len(cells),
                       columns=int(sum(len(ts_of[c]) for c in cells)))
                out, iters, _ = cv_mod.solve_columns_batched(
                    jnp.asarray(self.x_cells[cells]),
                    jnp.asarray(self.y_cells[cells]),
                    jnp.asarray(self.tmask_cells[cells]),
                    jnp.asarray(self.mask_cells[cells]),
                    jnp.asarray(self.gammas_cells[cells, g]),
                    jnp.asarray(np.stack(lam_b), jnp.float32),
                    jnp.asarray(np.stack(sub_b), jnp.float32),
                    jnp.asarray(np.stack(task_b), jnp.int32),
                    jnp.asarray(self.fold_keys[cells]),
                    jnp.asarray(np.stack(c0_b), jnp.float32),
                    self.cv_cfg)                         # (C, k, T*S), (C,)
                out = np.asarray(out)
            for i, c in enumerate(cells):
                for j, (t, s) in enumerate(ts_of[c]):
                    coefs[c, :, t, s] = out[i, :, j]
                    gamma[c, t, s] = self.gammas_cells[c, g]
                    lam[c, t, s] = self.lambdas[res.l_idx[c, t, s]]
                    val[c, t, s] = self.surf_loss[c, g, t,
                                                  res.l_idx[c, t, s], s]
                stats["columns_resolved"] += len(ts_of[c])
                m_resolved.inc(len(ts_of[c]))
            stats["resolve_calls"] += 1
            stats["solver_iters"] += int(np.asarray(iters).sum())

        return SelectResult(
            rule=rule, config=cfg, cv_cfg=self.cv_cfg, scaler=self.scaler,
            plan=self.plan, packed=self.packed, tasks=self.tasks,
            x_cells=self.x_cells, mask_cells=self.mask_cells,
            coefs=coefs, gamma=gamma, lam=lam, tau=self.tau.copy(),
            val_loss=val, extras=dict(res.extras), stats=stats,
            mesh=mesh, mesh_axes=mesh_axes)

    # ------------------------------------------------------ persistence
    _ARRAYS = ("lambdas", "gammas_cells", "fold_keys", "x_cells",
               "mask_cells", "y_cells", "tmask_cells", "coefs", "gamma",
               "lam", "tau", "val_loss", "surf_loss", "surf_fa", "surf_det")

    def save(self, ckpt_dir: str) -> str:
        tree = {k: getattr(self, k) for k in self._ARRAYS}
        tree.update(_ctx_tree(self.plan, self.packed, self.scaler, self.tasks))
        extra = _ctx_extra(self.config, self.cv_cfg, self.tasks, self.packed)
        extra.update(format=_TRAIN_FORMAT, n=self.n, d=self.d)
        return ckpt_mod.save_checkpoint(ckpt_dir, 0, tree, extra=extra,
                                        keep_last=0)

    @classmethod
    def load(cls, ckpt_dir: str) -> "TrainResult":
        tree, extra = _load_tree(ckpt_dir, _TRAIN_FORMAT)
        plan, packed, scaler, tasks = _ctx_from_tree(tree, extra)
        return cls(config=_cfg_from_json(SVMTrainerConfig, extra["config"]),
                   cv_cfg=_cfg_from_json(cv_mod.CVConfig, extra["cv_cfg"]),
                   scaler=scaler, plan=plan, packed=packed, tasks=tasks,
                   n=int(extra["n"]), d=int(extra["d"]),
                   **{k: tree[k] for k in cls._ARRAYS})


@dataclasses.dataclass
class SelectResult:
    """One selection outcome: final per-cell models + rule extras.

    Owns the test phase (``decision_function`` / ``predict`` /
    streaming ``test``) and the serving hand-off (``to_bank``).
    """
    rule: str
    config: SVMTrainerConfig
    cv_cfg: cv_mod.CVConfig
    scaler: Scaler
    plan: CellPlan
    packed: PackedCells
    tasks: TaskSet
    x_cells: np.ndarray
    mask_cells: np.ndarray
    coefs: np.ndarray          # (slots, k, T, S)
    gamma: np.ndarray          # (slots, T, S)
    lam: np.ndarray
    tau: np.ndarray
    val_loss: np.ndarray
    extras: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    mesh: Optional[Mesh] = None
    mesh_axes: Optional[Tuple[str, ...]] = None

    # -------------------------------------------------------- test phase
    @property
    def default_sub(self) -> int:
        """The sub column predictions read: the NP weight pick when the
        rule produced one, else column 0."""
        if "np_weight_idx" in self.extras:
            return int(np.asarray(self.extras["np_weight_idx"]).reshape(-1)[0])
        return 0

    def decision_function(self, x_test: np.ndarray) -> np.ndarray:
        """(m, d) raw features -> (m, T, S) via Voronoi cell routing."""
        xt = self.scaler.transform(np.asarray(x_test, np.float32))
        cell_of = self.plan.route(xt)
        slot_of = self.packed.slot_of_cell[cell_of]
        n_slots = self.packed.n_slots
        g = group_rows(slot_of, n_slots)
        # bucket the padded row count so repeated chunked calls (streamed
        # evaluation) hit one compiled shape; extra all-zero rows are
        # computed-then-dropped (row-independent)
        m_pad = -(-g.m_max // 8) * 8
        xt_cells = np.zeros((n_slots, m_pad, xt.shape[1]), np.float32)
        xt_cells[g.slot, g.pos] = xt[g.rows]

        dec = np.asarray(predict_cells(
            jnp.asarray(xt_cells), jnp.asarray(self.x_cells),
            jnp.asarray(self.coefs), jnp.asarray(self.gamma),
            kernel=self.config.kernel,
            mesh=self.mesh, axis_names=self.mesh_axes))

        out = np.zeros((xt.shape[0],) + dec.shape[2:], np.float32)
        out[g.rows] = dec[g.slot, g.pos]
        return out

    def predict(self, x_test: np.ndarray) -> np.ndarray:
        return combine_decisions(
            self.decision_function(x_test), self.config.scenario,
            classes=self.tasks.classes, pairs=self.tasks.pairs,
            sub=self.default_sub)

    def test(self, x_test, y_test, chunk_size: Optional[int] = None
             ) -> TestResult:
        """Stream the scenario error over any array / path / ChunkSource."""
        sc = self.config.scenario
        src: ChunkSource = as_source(x_test)
        y = np.asarray(y_test)
        chunk = int(chunk_size or self.config.chunk_size)
        taus = np.asarray(self.config.taus, np.float32)
        err_sum, den = 0.0, 0
        fa = det = neg = pos = 0
        for lo, block in src.iter_chunks(chunk):
            pred = self.predict(block)
            yc = y[lo:lo + block.shape[0]]
            if sc in ("binary", "weighted", "npsvm"):
                err_sum += float((pred != np.sign(yc)).sum())
                den += yc.shape[0]
                fa += int(((pred > 0) & (yc < 0)).sum())
                det += int(((pred > 0) & (yc > 0)).sum())
                neg += int((yc < 0).sum())
                pos += int((yc > 0).sum())
            elif sc in ("ova", "ava"):
                err_sum += float((pred != yc).sum())
                den += yc.shape[0]
            elif sc == "quantile":
                r = yc[:, None] - pred
                err_sum += float(np.where(r >= 0, taus * r,
                                          (taus - 1) * r).sum())
                den += r.size
            elif sc == "expectile":
                r = yc[:, None] - pred
                err_sum += float(np.where(r >= 0, taus * r * r,
                                          (1 - taus) * r * r).sum())
                den += r.size
            elif sc == "ls":
                err_sum += float(((pred - yc) ** 2).sum())
                den += yc.shape[0]
            else:
                raise ValueError(sc)
        details: Dict[str, float] = {}
        if neg + pos:
            details = {"false_alarm": fa / max(neg, 1),
                       "detection": det / max(pos, 1)}
        return TestResult(error=err_sum / max(den, 1), n=src.n_rows,
                          details=details)

    # ----------------------------------------------------------- serving
    def to_bank(self, drop_tol: float | None = 0.0, dtype: str = "f32",
                dedup: bool = True, version: int = 0):
        """Compact into a serving ModelBank (cold-starts ``SVMEngine``).

        A ``VORONOI=5`` (overlap) fit records ``routing="overlap"`` in the
        bank, so the engine blends the 2 nearest cells' decisions by
        default — the 2-cell ownership the models were trained on.
        ``version`` tags the bank for hot swapping
        (``SVMEngine.swap_bank`` accepts strictly newer versions only).
        """
        from repro.serve.model_bank import _FAR, ModelBank
        with obs.tracer.span("session.bank"):
            n_slots = self.packed.n_slots
            d = self.x_cells.shape[2]
            centers = np.full((n_slots, d), _FAR, np.float32)
            for s, cid in enumerate(self.packed.order):
                if cid >= 0:
                    centers[s] = self.plan.centers[cid]
            routing = "overlap" if self.config.cell_method == "overlap" \
                else "nearest"
            return ModelBank.from_cells(
                self.x_cells, self.mask_cells, self.coefs, self.gamma,
                centers, kernel=self.config.kernel, drop_tol=drop_tol,
                dtype=dtype, dedup=dedup,
                feat_mean=np.asarray(self.scaler.mean, np.float32),
                feat_std=np.asarray(self.scaler.std, np.float32),
                classes=self.tasks.classes, pairs=self.tasks.pairs,
                scenario=self.config.scenario, default_sub=self.default_sub,
                routing=routing, version=version)

    # ------------------------------------------------------ persistence
    _ARRAYS = ("x_cells", "mask_cells", "coefs", "gamma", "lam", "tau",
               "val_loss")
    _CELL_ARRAYS = ("x_cells", "mask_cells")   # the O(n·d) staged rows

    def save(self, ckpt_dir: str, train_ref: Optional[str] = None) -> str:
        """Persist the selection outcome.

        ``train_ref`` (a path relative to ``ckpt_dir``, e.g. ``"../train"``)
        skips re-writing the staged cell rows — the dominant O(n·d) arrays,
        identical for every re-selection of one fit — and records a
        reference to the TrainResult checkpoint that already holds them;
        the CLI uses this since ``train/`` always sits beside ``select/``.
        """
        skip = self._CELL_ARRAYS if train_ref is not None else ()
        tree = {k: getattr(self, k) for k in self._ARRAYS if k not in skip}
        tree.update(_ctx_tree(self.plan, self.packed, self.scaler, self.tasks))
        tree.update({f"extra_{k}": np.asarray(v)
                     for k, v in self.extras.items()})
        extra = _ctx_extra(self.config, self.cv_cfg, self.tasks, self.packed)
        extra.update(format=_SELECT_FORMAT, rule=self.rule, stats=self.stats,
                     train_ref=train_ref)
        return ckpt_mod.save_checkpoint(ckpt_dir, 0, tree, extra=extra,
                                        keep_last=0)

    @classmethod
    def load(cls, ckpt_dir: str) -> "SelectResult":
        tree, extra = _load_tree(ckpt_dir, _SELECT_FORMAT)
        plan, packed, scaler, tasks = _ctx_from_tree(tree, extra)
        extras = {k[len("extra_"):]: v for k, v in tree.items()
                  if k.startswith("extra_")}
        if extra.get("train_ref"):                 # cells live in train/
            ref = os.path.normpath(os.path.join(ckpt_dir, extra["train_ref"]))
            ref_tree, _ = _load_tree(ref, _TRAIN_FORMAT)
            for k in cls._CELL_ARRAYS:
                tree[k] = ref_tree[k]
        return cls(rule=extra["rule"],
                   config=_cfg_from_json(SVMTrainerConfig, extra["config"]),
                   cv_cfg=_cfg_from_json(cv_mod.CVConfig, extra["cv_cfg"]),
                   scaler=scaler, plan=plan, packed=packed, tasks=tasks,
                   extras=extras, stats=dict(extra.get("stats", {})),
                   **{k: tree[k] for k in cls._ARRAYS})


# ----------------------------------------------------------------- session
class SVM:
    """A staged session over one training set.

    ``x`` may be an (n, d) array or anything ``repro.pipeline`` can stream
    (memmap ``.npy`` path, npz shard list, custom ``ChunkSource``).  String
    config keys (the liquidSVM-style layer, see ``repro.api.config``) can
    be passed directly: ``SVM(x, y, scenario="binary", FOLDS=3)``.

    Token corpora: passing ``EMBED_ARCH`` (plus the other ``EMBED_*`` keys)
    flags ``x`` as a TOKEN source — it is wrapped with
    ``repro.embed.embed_source`` so training streams lazily-computed
    frozen-backbone embeddings.  ``y=None`` is accepted whenever ``x``
    carries its own labels (``repro.embed.LabeledSource`` or an
    ``EmbeddingSource`` built with ``labels=``): the label vector is then
    streamed from the source per wave instead of being required up front.
    """

    def __init__(self, x, y: Optional[np.ndarray] = None,
                 config: Optional[SVMTrainerConfig] = None,
                 mesh: Optional[Mesh] = None,
                 mesh_axes: Optional[Tuple[str, ...]] = None,
                 select_rule: Optional[str] = None,
                 select_kwargs: Optional[dict] = None,
                 serve_kwargs: Optional[dict] = None,
                 monitor_kwargs: Optional[dict] = None,
                 **config_keys):
        cfg = config or SVMTrainerConfig()
        sel_kw = dict(select_kwargs or {})
        srv_kw = dict(serve_kwargs or {})
        mon_kw = dict(monitor_kwargs or {})
        if config_keys:
            from repro.api.config import (apply_keys, split_embed_keys,
                                          split_monitor_keys, split_obs_keys,
                                          split_serve_keys)
            config_keys, key_obs = split_obs_keys(config_keys)
            if key_obs:
                obs.configure(**key_obs)
            config_keys, key_emb = split_embed_keys(config_keys)
            if key_emb:
                from repro.embed import embed_source
                x = embed_source(x, **key_emb)
            config_keys, key_mon = split_monitor_keys(config_keys)
            mon_kw = {**key_mon, **mon_kw}
            config_keys, key_srv = split_serve_keys(config_keys)
            srv_kw = {**key_srv, **srv_kw}
            cfg, key_sel = apply_keys(cfg, config_keys)
            sel_kw.update(key_sel)
        self.config = cfg
        self.mesh, self.mesh_axes = mesh, mesh_axes
        self.select_rule = select_rule
        self.select_kwargs = sel_kw
        self.serve_kwargs = srv_kw
        self.monitor_kwargs = mon_kw
        self._x, self._y = x, y
        self.train_result: Optional[TrainResult] = None
        self.select_result: Optional[SelectResult] = None

    # ------------------------------------------------------------- train
    def train(self, ckpt_dir: Optional[str] = None) -> TrainResult:
        """Solve the full fold x grid over all cells (wave-scheduled) and
        retain the validation surface.  ``ckpt_dir``: per-wave resume."""
        cfg = self.config
        x, y = self._x, self._y
        if y is None:
            if not hasattr(x, "labels_vector"):
                raise ValueError(
                    "SVM(y=None) needs a label-carrying x source "
                    "(repro.embed.LabeledSource, or an EmbeddingSource "
                    "built with labels=...) — plain feature sources "
                    "require an explicit y")
            # labels stream from the source: O(n) scalars assembled
            # chunk-by-chunk, never a caller-held per-shard copy
            y = x.labels_vector(cfg.chunk_size)

        raw_src: ChunkSource = as_source(x)
        with obs.tracer.span("session.scale"):
            if cfg.scale:
                scaler = Scaler.fit_stream(raw_src, cfg.chunk_size)
            else:
                scaler = Scaler(mean=np.zeros(raw_src.dim, np.float32),
                                std=np.ones(raw_src.dim, np.float32))
            if isinstance(raw_src, ArraySource):     # in-memory: scale once
                xs_src: ChunkSource = ArraySource(
                    scaler.transform(raw_src.materialize()))
            else:                                    # out-of-core: lazily
                xs_src = ScaledSource(raw_src, scaler.mean, scaler.std)
        n, d = xs_src.shape

        scenario = "weighted" if cfg.scenario in ("weighted", "npsvm") \
            else cfg.scenario
        tasks: TaskSet = make_tasks(y, scenario, taus=cfg.taus,
                                    weights=cfg.weights)

        n_dev = 1
        if self.mesh is not None and self.mesh_axes is not None:
            n_dev = int(np.prod([self.mesh.shape[a] for a in self.mesh_axes]))
        with obs.tracer.span("session.cells"):
            plan: CellPlan = build_cells_stream(
                xs_src, cell_size=cfg.cell_size, method=cfg.cell_method,
                seed=cfg.seed, chunk_size=cfg.chunk_size)
            packed: PackedCells = pack_cells(plan, n_dev)

        k = plan.k_max
        n_slots = packed.n_slots
        t_count = tasks.n_tasks
        cv_cfg = cv_mod.CVConfig(
            solver=cfg.resolve_solver(), kernel=cfg.kernel,
            n_folds=cfg.n_folds, fold_scheme=cfg.fold_scheme, tol=cfg.tol,
            max_iters=cfg.max_iters, taus=cfg.taus, weights=cfg.weights,
            keep_surface=True)

        base_grid = grids.liquid_grid(n=k, dim=d, median_dist=1.0,
                                      grid_choice=cfg.grid_choice,
                                      cell_size=cfg.cell_size)
        if cfg.adaptivity_control > 0:
            base_grid = grids.adaptive_subgrid(base_grid,
                                               cfg.adaptivity_control)
        n_gamma = len(base_grid.gammas)
        keys_all = np.asarray(
            jax.random.split(jax.random.PRNGKey(cfg.seed), n_slots))

        # the model + re-solve context: stage() fills these as a side effect
        # so the source is read ONCE; slots of checkpoint-restored waves are
        # back-filled afterwards (same deterministic computation).
        x_cells = np.zeros((n_slots, k, d), np.float32)
        mask_cells = np.zeros((n_slots, k), np.float32)
        y_cells = np.zeros((n_slots, t_count, k), np.float32)
        tmask_cells = np.zeros((n_slots, t_count, k), np.float32)
        gam_cells = np.ones((n_slots, n_gamma), np.float32)
        staged = np.zeros(n_slots, bool)

        def cell_gammas(x_c: np.ndarray, m: np.ndarray) -> np.ndarray:
            # per-cell adaptive gamma endpoints (paper: grid scaled per cell)
            med = float(kernel_fns.median_heuristic(jnp.asarray(x_c),
                                                    jnp.asarray(m)))
            g = grids.liquid_grid(n=int(m.sum()), dim=d, median_dist=med,
                                  grid_choice=cfg.grid_choice,
                                  cell_size=cfg.cell_size)
            if cfg.adaptivity_control > 0:
                g = grids.adaptive_subgrid(g, cfg.adaptivity_control)
            return np.asarray(g.gammas, np.float32)

        def stage(lo: int, hi: int):
            """Host arrays for slots [lo, hi) ONLY — O(wave) staging.

            Slots past n_slots (wave padding) stay empty: zero masks, unit
            gammas, zero keys — the same shape the planner's -1 slots get.
            """
            w = hi - lo
            x_w = np.zeros((w, k, d), np.float32)
            mask_w = np.zeros((w, k), np.float32)
            y_w = np.zeros((w, t_count, k), np.float32)
            tmask_w = np.zeros((w, t_count, k), np.float32)
            gam_w = np.ones((w, n_gamma), np.float32)
            keys_w = np.zeros((w,) + keys_all.shape[1:], keys_all.dtype)
            keys_w[: max(min(hi, n_slots) - lo, 0)] = keys_all[lo:hi]
            for j, s in enumerate(range(lo, min(hi, n_slots))):
                staged[s] = True
                cid = packed.order[s]
                if cid < 0:
                    continue
                ids = plan.indices[cid]
                m = plan.mask[cid]
                x_w[j] = xs_src.gather(ids)
                mask_w[j] = m
                y_w[j] = tasks.labels[:, ids] * m[None, :]
                tmask_w[j] = tasks.task_mask[:, ids] * m[None, :]
                gam_w[j] = cell_gammas(x_w[j], m)
                x_cells[s], mask_cells[s] = x_w[j], m
                y_cells[s], tmask_cells[s] = y_w[j], tmask_w[j]
                gam_cells[s] = gam_w[j]
            return x_w, y_w, tmask_w, mask_w, gam_w, keys_w

        lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(
            base_grid, cv_cfg, t_count)

        fingerprint = self._fingerprint(cv_cfg, plan, tasks, n, d)
        (coefs, gamma, lam, tau, val,
         surf_loss, surf_fa, surf_det) = train_cells_waves(
            stage, n_slots, cfg.n_slots_per_wave,
            lam_c, sub_c, task_c, cv_cfg, n_lam, n_sub,
            mesh=self.mesh, axis_names=self.mesh_axes, ckpt_dir=ckpt_dir,
            fingerprint=fingerprint)

        for s in np.flatnonzero(~staged):   # waves restored from checkpoint
            cid = packed.order[s]
            if cid >= 0:
                ids = plan.indices[cid]
                m = plan.mask[cid]
                x_cells[s] = xs_src.gather(ids)
                mask_cells[s] = m
                y_cells[s] = tasks.labels[:, ids] * m[None, :]
                tmask_cells[s] = tasks.task_mask[:, ids] * m[None, :]
                gam_cells[s] = cell_gammas(x_cells[s], m)

        self.train_result = TrainResult(
            config=cfg, cv_cfg=cv_cfg, scaler=scaler, plan=plan,
            packed=packed, tasks=tasks,
            lambdas=np.asarray(base_grid.lambdas, np.float32),
            gammas_cells=gam_cells, fold_keys=keys_all,
            x_cells=x_cells, mask_cells=mask_cells,
            y_cells=y_cells, tmask_cells=tmask_cells,
            coefs=np.asarray(coefs), gamma=np.asarray(gamma),
            lam=np.asarray(lam), tau=np.asarray(tau),
            val_loss=np.asarray(val), surf_loss=np.asarray(surf_loss),
            surf_fa=np.asarray(surf_fa), surf_det=np.asarray(surf_det),
            n=n, d=d)
        self.select_result = None
        return self.train_result

    def _fingerprint(self, cv_cfg, plan: CellPlan, tasks: TaskSet,
                     n: int, d: int) -> str:
        """Identity of this fit for wave-checkpoint resume: config, data
        layout (cell plan) and labels — a stale ckpt_dir from a different
        run must be rejected, not silently restored."""
        import hashlib
        h = hashlib.blake2b(digest_size=16)
        h.update(repr(self.config).encode())
        h.update(repr(cv_cfg).encode())
        h.update(np.int64([n, d]).tobytes())
        h.update(plan.indices.tobytes())
        h.update(plan.mask.tobytes())
        h.update(plan.centers.tobytes())
        h.update(np.ascontiguousarray(tasks.labels).tobytes())
        return h.hexdigest()

    # ------------------------------------------------------------ select
    def select(self, rule: Optional[str] = None, **rule_kwargs
               ) -> SelectResult:
        """Pick hyper-parameters over the retained surface (re-runnable)."""
        if self.train_result is None:
            raise RuntimeError("call train() before select()")
        merged = {**self.select_kwargs, **rule_kwargs}
        with obs.tracer.span("session.select"):
            self.select_result = self.train_result.select(
                rule or self.select_rule, mesh=self.mesh,
                mesh_axes=self.mesh_axes, **merged)
        return self.select_result

    # -------------------------------------------------------------- test
    def test(self, x_test, y_test,
             chunk_size: Optional[int] = None) -> TestResult:
        """Streamed scenario error; selects with the session default rule
        first if select() has not been called."""
        if self.select_result is None:
            self.select()
        return self.select_result.test(x_test, y_test, chunk_size=chunk_size)

    # ------------------------------------------------------------- serve
    def engine(self, **engine_kwargs):
        """Compact the selection into a bank and build an ``SVMEngine``.

        Serve-stage string keys given at session construction
        (``SERVE_OVERLAP``, ``DEADLINE_MS``) carry through here; explicit
        ``engine_kwargs`` win.  Selects with the session default rule first
        if ``select()`` has not been called.
        """
        if self.select_result is None:
            self.select()
        from repro.serve.svm_engine import SVMEngine
        return SVMEngine(self.select_result.to_bank(),
                         **{**self.serve_kwargs, **engine_kwargs})

    def monitor(self, engine, **monitor_kwargs):
        """Attach a :class:`repro.serve.HealthMonitor` to an engine.

        Monitor-stage string keys given at session construction
        (``SLO_P99_MS``, ``DRIFT_WINDOW``, ``DRIFT_REFRESH_THRESHOLD``)
        carry through here; explicit ``monitor_kwargs`` win.
        """
        from repro.serve.monitor import HealthMonitor
        return HealthMonitor(engine,
                             **{**self.monitor_kwargs, **monitor_kwargs})
