"""Driver ``train_waves_ls``: the wave scheduler over a regression cell plan,
timed.  ``train_waves`` does the work; this driver gives it the regression
rows and its own check.

Set-up, window and ``train_rows_per_s`` are ``train_waves``': the plan
built by the program's own ``Scaler``, ``build_cells_stream`` and
``pack_cells``, one warm-up wave, then ONE ``train_cells_waves`` call over
whole waves spread evenly over the plan in an order drawn from the seed;
the rate is the real rows of the window's cells over its wall time.  Only
the rows differ: ``data_reg.regression_rows`` (YearPredictionMSD's shape,
target centred on the training split's mean year) in place of
``cellplan``'s binary rows.

The check: for ``reference_cells`` of the window's cells, drawn from the
seed, ``reference_ls.cv_cell`` runs the same CV from the raw rows (its own
float64 scaling, grids and the cell's fold key); :func:`ls_numbers` sets
the window's output against it.  :func:`nonfinite` counts, over every cell
of the window, the non-finite numbers it returned.
"""
from __future__ import annotations

import contextlib
import os
import re
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import data_reg  # noqa: E402
import reference_ls  # noqa: E402
import train_waves  # noqa: E402
from compare import worst  # noqa: E402


def build(ctx):
    """``cellplan.build`` over the regression rows: the same program
    functions, the same keys."""
    from repro.data.scaling import Scaler
    from repro.distributed.planner import pack_cells
    from repro.pipeline.cell_stream import build_cells_stream
    from repro.pipeline.dataset import ArraySource, as_source
    cfg = ctx.cfg
    xtr, ytr, xte, yte, mean_year = data_reg.regression_rows(cfg["data"])
    chunk = cfg["cells"].get("chunk_size", 65536)
    scaler = Scaler.fit_stream(as_source(xtr), chunk)
    xs = scaler.transform(xtr)
    plan = build_cells_stream(ArraySource(xs), cell_size=cfg["cells"]["size"],
                              method=cfg["cells"]["method"],
                              seed=cfg["cells"]["plan_seed"], chunk_size=chunk)
    packed = pack_cells(plan, ctx.chips)
    return {"xtr": xtr, "ytr": ytr, "xte": xte, "yte": yte, "xs": xs,
            "plan": plan, "packed": packed, "mean_year": mean_year}


class _Plan:
    build = staticmethod(build)


@contextlib.contextmanager
def _regression_plan():
    """``train_waves`` builds its plan through its module's ``cellplan``;
    for the length of the call that name is this driver's :func:`build`."""
    saved = train_waves.cellplan
    train_waves.cellplan = _Plan
    try:
        yield
    finally:
        train_waves.cellplan = saved


def _require_batched_solve(ctx):
    """Stop before any data is made if the program's training wave holds
    an eigh: on the chip JAX's eigh at this cell's k_max compiles for
    minutes and runs the wave's lanes one after another (~57 s a wave on
    a v5e), far past the window.  The probe traces ``train_cells`` at a
    toy shape with the configuration's CV settings; it compiles nothing."""
    import jax
    import jax.numpy as jnp
    from repro.core import cv as cv_mod
    from repro.core import grids
    from repro.distributed import cell_trainer
    cvc = ctx.cfg["cv"]
    cfg = cv_mod.CVConfig(
        solver=cvc["solver"], kernel=cvc["kernel"], n_folds=cvc["folds"],
        fold_scheme=cvc["fold_scheme"], tol=cvc["tol"],
        max_iters=cvc["max_iters"], gram_dtype=cvc["gram_dtype"],
        taus=tuple(cvc["taus"]), weights=tuple(cvc["weights"]),
        keep_surface=True)
    k, d = 4 * cvc["folds"], 2
    base = grids.liquid_grid(n=k, dim=d, median_dist=1.0,
                             grid_choice=cvc["grid_choice"],
                             cell_size=ctx.cfg["cells"]["size"])
    cols = cv_mod.grid_columns(base, cfg, 1)
    ones = jnp.ones((1, 1, k), jnp.float32)
    args = (jnp.zeros((1, k, d), jnp.float32), ones, ones,
            jnp.ones((1, k), jnp.float32),
            jnp.asarray(base.gammas, jnp.float32)[None],
            jax.random.split(jax.random.PRNGKey(0), 1))
    jaxpr = jax.make_jaxpr(lambda *a: cell_trainer.train_cells(
        *a, cols[0], cols[1], cols[2], cfg, cols[3], cols[4]))(*args)
    if re.search(r"\beigh\[", str(jaxpr)):
        raise SystemExit("error: the program's training wave holds an eigh, "
                         "which the chip cannot run at this cell's k_max in "
                         "its time; the cell cannot run")


def setup(ctx):
    _require_batched_solve(ctx)
    with _regression_plan():
        return train_waves.setup(ctx)


window = train_waves.window


def ls_numbers(ref: dict, c_prog, g_prog: float, l_prog: float, surf_prog,
               gammas, lambdas) -> dict:
    """The numbers compared for one working set.

    ``ref`` is :func:`reference_ls.cv_cell`'s output; ``c_prog`` (n,) the
    program's fold-averaged model, ``g_prog``/``l_prog`` its selected
    gamma and lambda (matched to the reference's grid), ``surf_prog``
    (G, L) its validation surface (mean squared error).

    * ``coef_gap``: max |c - c_ref| / max |c_ref| at the program's (γ, λ);
    * ``surface_gap``: the widest relative gap of the 100-point surface,
      max |s - s_ref| / s_ref;
    * ``select_regret``: what the program's choice costs on the
      reference's surface, relative to the reference's least MSE.
    """
    gi = int(np.argmin(np.abs(np.log(gammas / g_prog))))
    li = int(np.argmin(np.abs(np.log(lambdas / l_prog))))
    c_ref = ref["coefs"][gi, :, li]
    s_ref = ref["surface"]
    return {
        "coef_gap": float(np.max(np.abs(c_prog - c_ref))
                          / max(float(np.max(np.abs(c_ref))), 1e-30)),
        "surface_gap": float(np.max(np.abs(surf_prog - s_ref) / s_ref)),
        "select_regret": float((s_ref[gi, li] - s_ref.min()) / s_ref.min()),
    }


def nonfinite(st, coefs, gamma, lam, surf) -> int:
    """The non-finite coefficients, selected (gamma, lambda) and surface
    entries over every real cell of the window: a factorisation that broke
    down, or a fault of one slot, in a cell the reference does not
    sample."""
    packed = st["packed"]
    live = [j for j, s in enumerate(st["slots"])
            if s < packed.n_slots and packed.order[s] >= 0]
    return int(sum(np.size(a[live]) - np.isfinite(a[live]).sum()
                   for a in (np.asarray(coefs), np.asarray(gamma),
                             np.asarray(lam), np.asarray(surf))))


def check(ctx):
    """The compared numbers, worst over the reference cells, and the
    non-finite count over every cell of the window."""
    w = ctx.window
    st = w.pop("state")
    coefs, gamma, lam, _, _, surf = w.pop("out")[:6]
    out = {}
    for j, inp, _ in train_waves._reference_cells(ctx, st):
        ref = reference_ls.cv_cell(**inp, n_folds=ctx.cfg["cv"]["folds"])
        out = worst(out, ls_numbers(
            ref, coefs[j, :, 0, 0], float(gamma[j, 0, 0]),
            float(lam[j, 0, 0]), surf[j, :, 0, :, 0], inp["gammas"],
            inp["lambdas"]))
    out["nonfinite"] = nonfinite(st, coefs, gamma, lam, surf)
    return out


def stand_in(ctx, ref):
    """The record the check reads, with ``ref`` (a copy of the reference
    at a lower precision) in the program's place for the cells it
    compares: the plan and the window's slots as set-up makes them, no
    wave run."""
    with _regression_plan():
        st = train_waves._prepare(ctx)
    n, g, l = len(st["slots"]), st["n_gamma"], st["n_lam"]
    k = st["k"]
    coefs = np.zeros((n, k, 1, 1), np.float32)
    gamma = np.ones((n, 1, 1), np.float32)
    lam = np.ones((n, 1, 1), np.float32)
    surf = np.zeros((n, g, 1, l, 1), np.float32)
    for j, inp, _ in train_waves._reference_cells(ctx, st):
        r = ref.cv_cell(**inp, n_folds=ctx.cfg["cv"]["folds"])
        gi, li = r["g_idx"], r["l_idx"]
        coefs[j, :, 0, 0] = r["coefs"][gi, :, li]
        gamma[j] = inp["gammas"][gi]
        lam[j] = inp["lambdas"][li]
        surf[j, :, 0, :, 0] = r["surface"]
    return {"out": (coefs, gamma, lam, None, None, surf), "state": st}
