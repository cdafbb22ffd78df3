"""Driver ``train_waves``: the wave scheduler over a cell plan, timed.

Set-up makes the configuration's data set, scales it and builds and packs
the cell plan with the program's own functions (``Scaler``,
``build_cells_stream``, ``pack_cells``), draws the fold keys from the seed,
and warms up with one wave that the window does not time.

The window is ONE ``train_cells_waves`` call over ``m`` waves of the plan,
``m = max(min_waves, round(seconds / wave_s))`` (``wave_s`` is the traffic
file's measured seconds per wave), spread evenly over the plan's waves so
that small and large cells are both timed, in an order drawn from the seed.
Its ``stage`` is a copy of ``SVM.train``'s staging closure: gather, labels,
task mask, per-cell gamma grid (``median_heuristic``, ``liquid_grid``),
fold keys.  ``train_rows_per_s`` is the real (unpadded) rows of the
window's cells over the window's wall time.

The check: for ``reference_cells`` of the window's cells, drawn from the
seed, the plain reference (``reference.cv_cell``) runs the same CV from the
raw rows; ``compare.cv_numbers`` sets what the window returned against it.
"""
from __future__ import annotations

import time

import numpy as np

import cellplan
import data as bdata
import reference
from compare import cv_numbers, worst


def _prepare(ctx):
    """Everything but the warm-up wave: plan, grids, keys, waves, stage."""
    import jax
    import jax.numpy as jnp
    from repro.core import cv as cv_mod
    from repro.core import grids, kernel_fns
    cfg, tr = ctx.cfg, ctx.traffic
    st = cellplan.build(ctx)
    plan, packed, xs, ytr = st["plan"], st["packed"], st["xs"], st["ytr"]
    cvc = cfg["cv"]
    k, d = plan.k_max, xs.shape[1]
    cell_size = cfg["cells"]["size"]
    cv_cfg = cv_mod.CVConfig(
        solver=cvc["solver"], kernel=cvc["kernel"], n_folds=cvc["folds"],
        fold_scheme=cvc["fold_scheme"], tol=cvc["tol"],
        max_iters=cvc["max_iters"], gram_dtype=cvc["gram_dtype"],
        taus=tuple(cvc["taus"]), weights=tuple(cvc["weights"]),
        keep_surface=True)
    base = grids.liquid_grid(n=k, dim=d, median_dist=1.0,
                             grid_choice=cvc["grid_choice"],
                             cell_size=cell_size)
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(base, cv_cfg, 1)
    fold_seed = bdata.seeds(ctx.seed, 1)[0]
    keys_all = np.asarray(jax.random.split(jax.random.PRNGKey(fold_seed),
                                           packed.n_slots))

    def cell_gammas(x_c, m):
        med = float(kernel_fns.median_heuristic(jnp.asarray(x_c),
                                                jnp.asarray(m)))
        g = grids.liquid_grid(n=int(m.sum()), dim=d, median_dist=med,
                              grid_choice=cvc["grid_choice"],
                              cell_size=cell_size)
        return np.asarray(g.gammas, np.float32)

    slots_per_wave = tr["slots_per_wave"] * ctx.chips
    n_waves = -(-packed.n_slots // slots_per_wave)
    m = max(int(tr["min_waves"]), int(round(ctx.seconds / tr["wave_s"])))
    m = min(m, n_waves - 1)
    picked = [int((i + 0.5) * n_waves / m) for i in range(m)]
    picked = list(np.random.default_rng(fold_seed).permutation(picked))
    warm = next(w for w in range(n_waves) if w not in picked)

    def slots_of(waves):
        return [w * slots_per_wave + j for w in waves
                for j in range(slots_per_wave)]

    def make_stage(slots):
        """``SVM.train``'s stage closure over an explicit slot list."""
        def stage(lo, hi):
            w = hi - lo
            x_w = np.zeros((w, k, d), np.float32)
            mask_w = np.zeros((w, k), np.float32)
            y_w = np.zeros((w, 1, k), np.float32)
            tmask_w = np.zeros((w, 1, k), np.float32)
            gam_w = np.ones((w, len(base.gammas)), np.float32)
            keys_w = np.zeros((w,) + keys_all.shape[1:], keys_all.dtype)
            for j, s in enumerate(slots[lo:hi]):
                if s >= packed.n_slots or packed.order[s] < 0:
                    continue
                cid = packed.order[s]
                ids, msk = plan.indices[cid], plan.mask[cid]
                x_w[j] = xs[ids]
                mask_w[j] = msk
                y_w[j, 0] = ytr[ids] * msk
                tmask_w[j, 0] = msk
                gam_w[j] = cell_gammas(x_w[j], msk)
                keys_w[j] = keys_all[s]
            return x_w, y_w, tmask_w, mask_w, gam_w, keys_w
        return stage

    mesh = axes = None
    if ctx.chips > 1:
        from jax.sharding import Mesh
        mesh, axes = Mesh(np.asarray(jax.devices()[:ctx.chips]),
                          ("data",)), ("data",)

    def call(slots):
        from repro.distributed.cell_trainer import train_cells_waves
        return train_cells_waves(make_stage(slots), len(slots),
                                 slots_per_wave, lam_c, sub_c, task_c, cv_cfg,
                                 n_lam, n_sub, mesh=mesh, axis_names=axes)

    st.update(call=call, slots=slots_of(picked), picked=picked,
              warm_slots=slots_of([warm]), n_waves=n_waves,
              fold_seed=fold_seed, k=k, d=d, n_gamma=len(base.gammas),
              n_lam=n_lam)
    return st


def setup(ctx):
    st = _prepare(ctx)
    t = time.perf_counter()
    st["call"](st["warm_slots"])
    st["warm_s"] = time.perf_counter() - t
    return st


def window(ctx, st):
    t0 = time.perf_counter()
    out = st["call"](st["slots"])
    wall = time.perf_counter() - t0
    plan, packed = st["plan"], st["packed"]
    cells = [int(packed.order[s]) for s in st["slots"]
             if s < packed.n_slots and packed.order[s] >= 0]
    sizes = [int(plan.mask[c].sum()) for c in cells]
    rows = sum(sizes)
    cvc = ctx.cfg["cv"]
    return {
        "wall_s": wall,
        "attempted": len(cells), "failed": 0,
        "end_to_end": {"train_rows_per_s": rows / wall},
        "out": out,
        "work": {"k": st["k"], "d": st["d"], "slots": len(st["slots"]),
                 "n_gamma": st["n_gamma"], "folds": cvc["folds"],
                 "p": st["n_lam"], "sizes": sizes},
        "notes": {"waves": len(st["picked"]), "plan_waves": st["n_waves"],
                  "cells": len(cells), "rows": rows, "k_max": st["k"],
                  "warm_wave_s": round(st["warm_s"], 6)},
        "state": st,
    }


def _reference_cells(ctx, st):
    """The window's slots the check compares, drawn from the seed, each
    with the reference's own inputs: rows scaled by float64 statistics of
    the raw training rows, its own gamma and lambda grids, the fold key."""
    import jax
    plan, packed = st["plan"], st["packed"]
    rng = np.random.default_rng(st["fold_seed"] + 1)
    live = [j for j, s in enumerate(st["slots"])
            if s < packed.n_slots and packed.order[s] >= 0]
    pick = rng.choice(live, size=min(ctx.traffic["reference_cells"],
                                     len(live)), replace=False)
    xtr = st["xtr"].astype(np.float64)
    mean, std = xtr.mean(0), xtr.std(0)
    std = np.where(std > 0, std, 1.0)
    size = ctx.cfg["cells"]["size"]
    _, lams = reference.liquid_grid(st["k"], st["d"], 1.0, size)
    keys_all = np.asarray(jax.random.split(jax.random.PRNGKey(st["fold_seed"]),
                                           packed.n_slots))
    for j in pick:
        s = st["slots"][j]
        ids, msk = plan.indices[packed.order[s]], plan.mask[packed.order[s]]
        x = (((xtr[ids] - mean) / std) * msk[:, None]).astype(np.float32)
        n_real = int(msk.sum())
        gam, _ = reference.liquid_grid(n_real, st["d"],
                                       reference.median_dist(x, msk), size)
        key = keys_all[s]
        yield int(j), dict(x=x, y=st["ytr"][ids] * msk, mask=msk,
                           gammas=gam, lambdas=lams, fold_key=key), n_real


def check(ctx):
    """The compared numbers, worst over the reference cells."""
    w = ctx.window
    st = w.pop("state")
    coefs, gamma, lam, _, _, surf = w.pop("out")[:6]
    cvc = ctx.cfg["cv"]
    out = {}
    for j, inp, n_real in _reference_cells(ctx, st):
        ref = reference.cv_cell(**inp, n_folds=cvc["folds"], tol=cvc["tol"],
                                max_iters=cvc["max_iters"])
        out = worst(out, cv_numbers(
            ref, coefs[j, :, 0, 0], float(gamma[j, 0, 0]),
            float(lam[j, 0, 0]), surf[j, :, 0, :, 0], inp["gammas"],
            inp["lambdas"], n_real))
    return out
