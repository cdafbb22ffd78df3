"""Mesh-sharded cell training — the TPU analogue of the paper's Spark layer.

The paper (Table 4): coarse Voronoi cells are shuffled to Spark workers;
each worker solves its coarse cell via fine cells of <= 2000.  Here:

  * fine cells are padded + bin-packed (repro.distributed.planner) and laid
    out as one (n_slots, k, ...) batch;
  * the slot axis is sharded over EVERY mesh axis (pod x data x model) with
    shard_map — 512 chips solve 512 cell-batches concurrently;
  * inside a shard, vmap over local slots and the fused CV+selection
    (repro.core.cv.cv_cell) does the per-cell work — within which the
    hyper-parameter grid is itself GEMM-batched.  Three nested levels of
    parallelism, zero inter-device communication during the solve phase
    (embarrassingly parallel by construction — the paper's observed
    superlinear Spark speedup is the same effect).

Test phase: test points are routed host-side to their owning cell
(nearest center — Voronoi routing), padded per slot, and evaluated with
the same sharding.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import cv as cv_mod
from repro.core import kernel_fns, select

Array = jax.Array

def _cell_train_local(x_c, y_c, tmask_c, mask_c, gammas_c, key_c,
                      lam_c, sub_c, task_c, cfg, n_lam, n_sub):
    """vmap body: one cell."""
    sel = cv_mod.cv_cell(x_c, y_c, tmask_c, mask_c, gammas_c,
                         lam_c, sub_c, task_c, key_c, cfg,
                         n_lam=n_lam, n_sub=n_sub)
    combined = select.combine_fold_models(sel.coefs)      # (n, T, S)
    out = (combined, sel.gamma, sel.lam, sel.tau, sel.val_loss)
    if cfg.keep_surface:
        out = out + (sel.val_grid, sel.fa_grid, sel.det_grid)
    return out + (sel.iters,)


@functools.partial(jax.jit, static_argnames=("cfg", "n_lam", "n_sub", "mesh", "axis_names"))
def train_cells(
    x_cells: Array,        # (n_slots, k, d)
    y_cells: Array,        # (n_slots, n_tasks, k)
    tmask_cells: Array,    # (n_slots, n_tasks, k)
    mask_cells: Array,     # (n_slots, k)
    gammas_cells: Array,   # (n_slots, n_gamma) per-cell adaptive gamma grids
    keys: Array,           # (n_slots, 2) fold PRNG keys
    lam_c: Array, sub_c: Array, task_c: Array,
    cfg: cv_mod.CVConfig,
    n_lam: int, n_sub: int,
    mesh: Mesh | None = None,
    axis_names: Tuple[str, ...] | None = None,
):
    """Returns the :func:`wave_keys` arrays (coefs (n_slots, k, T, S),
    gamma/lam/tau/val (n_slots, T, S), the surface with
    ``cfg.keep_surface``), then the box-QP iteration counts
    (n_slots, n_gamma, n_folds): a diagnostic, not a wave key."""
    body = functools.partial(_cell_train_local, lam_c=lam_c, sub_c=sub_c,
                             task_c=task_c, cfg=cfg, n_lam=n_lam, n_sub=n_sub)
    vbody = jax.vmap(body)
    if mesh is None:
        return vbody(x_cells, y_cells, tmask_cells, mask_cells, gammas_cells, keys)

    spec = P(axis_names)
    shard = jax.shard_map(
        vbody, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, spec),
        out_specs=(spec,) * (len(wave_keys(cfg)) + 1),
        check_vma=False,
    )
    return shard(x_cells, y_cells, tmask_cells, mask_cells, gammas_cells, keys)


# ------------------------------------------------------------------ waves
def fista_counts(iters: np.ndarray, mask: np.ndarray, max_iters: int,
                 n_dev: int = 1) -> dict:
    """One wave's box-QP counts from ``train_cells``' iteration output.

    ``iters`` (slots, n_gamma, n_folds); ``mask`` (slots, k) marks real
    rows, so a slot with none is padding.  ``solves``, ``iters`` and
    ``capped`` (stopped at ``max_iters``) count real slots only.
    ``lane_iters`` is what the batched loop ran: the slots are split
    evenly over ``n_dev`` devices in order, and on each device, for each
    gamma, every (slot, fold) lane it carries, padding included, runs as
    many iterations as its slowest lane.
    """
    real = np.asarray(mask).reshape(mask.shape[0], -1).sum(axis=1) > 0
    it = np.asarray(iters, np.int64)
    s, g, f = it.shape
    per_dev = it.reshape(n_dev, s // n_dev, g, f)
    lane = int(per_dev.max(axis=(1, 3)).sum()) * (s // n_dev) * f
    mine = it[real]
    return {"solves": int(mine.size), "iters": int(mine.sum()),
            "capped": int((mine >= max_iters).sum()), "lane_iters": lane}


def ls_counts(mask: np.ndarray, n_gamma: int, n_folds: int) -> dict:
    """One wave's least-squares counts: ``paths`` is the lambda paths the
    real slots solved, one per (slot, gamma, fold): every lambda of one
    fold's training block at one gamma, however it is factorised;
    ``lane_paths`` is what the device ran, padding slots included.
    ``factor_rows`` is the side of the block each lane path factors
    (``cv.ls_factor_side``) times ``lane_paths``; ``train_rows`` the real
    training rows in them, ``(n_folds - 1)`` times each real row per gamma
    (every real row validates in exactly one fold).  Their ratio is the
    factor's fill."""
    rows = np.asarray(mask).reshape(mask.shape[0], -1) > 0
    real = rows.any(axis=1)
    per_slot = n_gamma * n_folds
    lane_paths = int(real.size) * per_slot
    side = cv_mod.ls_factor_side(rows.shape[1], n_folds)
    return {"paths": int(real.sum()) * per_slot, "lane_paths": lane_paths,
            "factor_rows": side * lane_paths,
            "train_rows": (n_folds - 1) * int(rows.sum()) * n_gamma}


_WAVE_KEYS = ("coefs", "gamma", "lam", "tau", "val")
_SURFACE_KEYS = ("surf_loss", "surf_fa", "surf_det")


def wave_keys(cfg: cv_mod.CVConfig) -> Tuple[str, ...]:
    """Names (in output order) of the arrays one wave produces.

    With ``cfg.keep_surface`` the per-cell validation surface — loss plus
    hinge FA/detection counts over the whole (gamma, task, lambda, sub)
    grid — rides along; it is O(slots · grid), tiny next to the coefs, and
    is what makes the staged ``select()`` phase re-runnable without
    retraining.
    """
    return _WAVE_KEYS + (_SURFACE_KEYS if cfg.keep_surface else ())


def train_cells_waves(
    stage,
    n_slots: int,
    wave_size: int | None,
    lam_c: Array, sub_c: Array, task_c: Array,
    cfg: cv_mod.CVConfig,
    n_lam: int, n_sub: int,
    mesh: Mesh | None = None,
    axis_names: Tuple[str, ...] | None = None,
    ckpt_dir: str | None = None,
    fingerprint: str | None = None,
):
    """Wave-scheduled :func:`train_cells`: bounded staging at any n_slots.

    ``stage(lo, hi)`` materializes ONLY slots [lo, hi) — six host arrays
    ``(x, y, tmask, mask, gammas, keys)`` whose leading axis is
    ``hi - lo`` (slots past ``n_slots`` must be empty padding: zero masks).
    Every wave has the same padded slot count, so the jitted/sharded
    ``train_cells`` compiles once and peak staging memory is
    O(wave · k · d) instead of O(n_slots · k · d).

    ``ckpt_dir`` checkpoints each completed wave through
    ``repro.train.checkpoint`` (step == wave index, all waves kept); a
    re-run with the same directory, wave size, slot count AND
    ``fingerprint`` (the caller's hash of config + data identity —
    ``LiquidSVM`` passes one) restores finished waves instead of
    re-solving them — mid-fit fault tolerance for multi-hour cell sweeps.
    A mismatched fingerprint means a different run left the directory:
    its waves are ignored and re-solved.

    Preemption survival: each wave is matched INDIVIDUALLY against the
    directory (not just the latest step), so a kill at any point — mid
    checkpoint write, mid solve, between waves — leaves only complete,
    checksummed wave dirs behind; the re-run restores those and re-solves
    the rest, and the solve being deterministic per wave makes the final
    models bitwise identical to an uninterrupted run.  A wave dir that
    fails checksum verification (torn write, bit rot) is re-solved, not
    loaded.
    """
    from repro import obs
    from repro.obs import jaxprof
    from repro.testing import faults
    from repro.train import checkpoint as ckpt_mod

    m_solved = obs.metrics.counter("train.waves_solved")
    m_restored = obs.metrics.counter("train.waves_restored")
    m_corrupt = obs.metrics.counter("train.corrupt_waves")
    box_qp = cfg.solver in ("hinge", "quantile")
    m_fista = {k: obs.metrics.counter("train.fista." + k)
               for k in ("solves", "iters", "capped", "lane_iters")}
    m_ls = {k: obs.metrics.counter("train.ls." + k)
            for k in ("paths", "lane_paths", "factor_rows", "train_rows")}

    keys_out = wave_keys(cfg)
    if wave_size is None or wave_size >= n_slots:
        wave_size = n_slots
    assert wave_size > 0
    n_dev = 1
    if mesh is not None and axis_names is not None:
        n_dev = int(np.prod([mesh.shape[a] for a in axis_names]))
        assert wave_size % n_dev == 0, (
            f"wave_size {wave_size} must divide over {n_dev} devices")
    n_waves = -(-n_slots // wave_size)

    restorable = set()
    if ckpt_dir is not None:
        for s in ckpt_mod.list_steps(ckpt_dir):
            try:
                extra = ckpt_mod.peek_manifest(ckpt_dir, s)["extra"]
            except ckpt_mod.CheckpointCorruptError:
                continue
            if (extra.get("wave_size") == wave_size
                    and extra.get("n_slots") == n_slots
                    and extra.get("fingerprint") == fingerprint):
                restorable.add(s)

    outs = []
    for w in range(n_waves):
        lo = w * wave_size
        faults.fire("trainer.wave.start", wave=w)
        res = None
        if w in restorable:
            with obs.tracer.span("train.wave.restore") as sp:
                try:
                    man = ckpt_mod.peek_manifest(ckpt_dir, w)
                    target = {k: np.zeros(s, np.dtype(dt)) for k, s, dt in zip(
                        sorted(keys_out), man["shapes"], man["dtypes"])}
                    tree, _, _ = ckpt_mod.restore_checkpoint(
                        ckpt_dir, target, step=w)
                    res = tuple(np.asarray(tree[k]) for k in keys_out)
                    m_restored.inc()
                except ckpt_mod.CheckpointCorruptError:
                    res = None             # torn/corrupt wave: re-solve it
                    m_corrupt.inc()
                    sp.set(wave=w, corrupt=True)
        if res is None:
            with obs.tracer.span("train.wave.stage"):
                arrays = stage(lo, lo + wave_size)
            with obs.tracer.span("train.wave.solve") as sp:
                sp.set(wave=w, slots=wave_size)
                args = [jnp.asarray(a) for a in arrays]
                if obs.tracer.enabled:
                    jaxprof.note(train_cells, *args, lam_c, sub_c, task_c,
                                 cfg, n_lam, n_sub, mesh=mesh,
                                 axis_names=axis_names)
                with jaxprof.step("train_wave", w):
                    res = train_cells(*args, lam_c, sub_c, task_c, cfg,
                                      n_lam, n_sub, mesh=mesh,
                                      axis_names=axis_names)
                    res = tuple(np.asarray(r) for r in res)
                res, iters = res[:-1], res[-1]
                if box_qp:
                    counts = fista_counts(iters, arrays[3], cfg.max_iters,
                                          n_dev)
                    for k, v in counts.items():
                        m_fista[k].inc(v)
                    sp.set(**{"fista_" + k: v for k, v in counts.items()})
                elif cfg.solver == "ls":
                    counts = ls_counts(arrays[3], arrays[4].shape[1],
                                       cfg.n_folds)
                    for k, v in counts.items():
                        m_ls[k].inc(v)
                    sp.set(**{"ls_" + k: v for k, v in counts.items()})
            m_solved.inc()
            faults.fire("trainer.wave.solved", wave=w)
            if ckpt_dir is not None:
                with obs.tracer.span("train.wave.checkpoint"):
                    ckpt_mod.save_checkpoint(
                        ckpt_dir, w, dict(zip(keys_out, res)),
                        extra={"wave": w, "wave_size": wave_size,
                               "n_slots": n_slots, "fingerprint": fingerprint},
                        keep_last=0)
        outs.append(res)
    return tuple(np.concatenate([o[i] for o in outs])[:n_slots]
                 for i in range(len(keys_out)))


def _cell_predict_local(xt_c, sv_c, coef_c, gamma_c, kernel: str):
    """xt_c (m, d); sv_c (k, d); coef_c (k, T, S); gamma_c (T, S).

    Cross-Gram distance cache: each (task, sub) may have selected a
    different gamma but shares the same (test, SV) point pair, so the
    O(m k d) cross term is computed once per cell and the per-gamma
    epilogue is replayed under vmap.
    """
    gram_of = kernel_fns.cross_gram_fn(xt_c, sv_c, kernel)

    def per_ts(gamma, coef):
        return jnp.matmul(gram_of(gamma), coef,
                          precision=jax.lax.Precision.HIGHEST)  # (m,)

    t, s = gamma_c.shape
    out = jax.vmap(per_ts)(gamma_c.reshape(-1), coef_c.reshape(coef_c.shape[0], -1).T)
    return out.T.reshape(xt_c.shape[0], t, s)            # (m, T, S)


@functools.partial(jax.jit, static_argnames=("kernel", "mesh", "axis_names"))
def predict_cells(
    xt_cells: Array,      # (n_slots, m_max, d) routed+padded test points
    sv_cells: Array,      # (n_slots, k, d)
    coef_cells: Array,    # (n_slots, k, T, S)
    gamma_cells: Array,   # (n_slots, T, S)
    kernel: str = "gauss_rbf",
    mesh: Mesh | None = None,
    axis_names: Tuple[str, ...] | None = None,
) -> Array:
    vbody = jax.vmap(functools.partial(_cell_predict_local, kernel=kernel))
    if mesh is None:
        return vbody(xt_cells, sv_cells, coef_cells, gamma_cells)
    spec = P(axis_names)
    shard = jax.shard_map(vbody, mesh=mesh,
                          in_specs=(spec, spec, spec, spec), out_specs=spec,
                          check_vma=False)
    return shard(xt_cells, sv_cells, coef_cells, gamma_cells)
