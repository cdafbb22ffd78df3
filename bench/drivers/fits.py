"""Driver ``fits``: whole small fits through the user entry point.

Set-up draws ``n_fits`` samples of the configuration's population
(``n_train`` rows each) from the traffic file's fixed pool seed, so every
run fits the same samples with the same folds, in an order drawn from
``--seed``, and warms up with one more fit that the window does not time.

The window: one fit after another, each ``SVM(x, y, ...).train()`` ->
``select()`` -> ``to_bank()``, ``n_fits = max(min_fits, round(seconds /
fit_s))`` (``fit_s`` is the traffic file's measured seconds per fit).
``train_rows_per_s`` is the fits' rows over the window's wall time.

The check: ``reference_fits`` of the window's fits, drawn from the seed,
go through the plain reference (``reference.cv_cell``) from their raw rows,
set against the window's output by ``compare.cv_numbers``.
"""
from __future__ import annotations

import contextlib
import functools
import time

import numpy as np

import data as bdata
import reference
from compare import cv_numbers, worst


def _session_keys(ctx, seed: int) -> dict:
    cvc, cells = ctx.cfg["cv"], ctx.cfg["cells"]
    return dict(SCENARIO="binary", VORONOI=cells["voronoi"],
                CELL_SIZE=cells["size"], FOLDS=cvc["folds"],
                MAX_ITERATIONS=cvc["max_iters"], TOLERANCE=cvc["tol"],
                RANDOM_SEED=seed)


@contextlib.contextmanager
def _gram_dtype(dtype: str):
    """The session takes no Gram dtype key: a dtype other than the default
    f32 (the control's bf16) is switched on as ``CVConfig``'s default."""
    if dtype == "f32":
        yield
        return
    from repro.core import cv
    real = cv.CVConfig
    cv.CVConfig = functools.partial(real, gram_dtype=dtype)
    try:
        yield
    finally:
        cv.CVConfig = real


def _fit(ctx, x, y, seed):
    from repro.api import SVM
    dtype = ctx.cfg["cv"]["gram_dtype"]
    with _gram_dtype(dtype):
        sess = SVM(x, y, **_session_keys(ctx, seed))
        tr = sess.train()
        sel = sess.select()
        bank = sel.to_bank()
    if tr.cv_cfg.gram_dtype != dtype:
        raise RuntimeError(f"the session solved with gram_dtype "
                           f"{tr.cv_cfg.gram_dtype!r}, not {dtype!r}")
    return tr, bank


def _draws(ctx):
    """The window's fits and one more for the warm-up: samples of the
    population from the traffic file's fixed ``pool_seed`` (each sample's
    seed is also its fit's ``RANDOM_SEED``, so its folds are fixed too),
    in an order drawn from ``--seed``."""
    tr = ctx.traffic
    n_fits = max(int(tr["min_fits"]), int(round(ctx.seconds / tr["fit_s"])))
    draws = []
    for s in bdata.seeds(tr["pool_seed"], n_fits + 1):
        x, y, _, _ = bdata.binary_rows(ctx.cfg["data"], sample_seed=s)
        draws.append((x, y, s))
    order = np.random.default_rng(ctx.seed % 2**63).permutation(n_fits)
    return {"draws": [draws[i] for i in order], "warm": draws[-1],
            "seed": ctx.seed}


def setup(ctx):
    st = _draws(ctx)
    t = time.perf_counter()
    _fit(ctx, *st["warm"])
    st["warm_s"] = time.perf_counter() - t
    return st


def window(ctx, st):
    from repro import obs
    outs, fit_s = [], []
    t0 = time.perf_counter()
    for x, y, s in st["draws"]:
        t = time.perf_counter()
        with obs.tracer.span("bench.fit"):
            tr, _ = _fit(ctx, x, y, s)
        fit_s.append(time.perf_counter() - t)
        outs.append((tr.coefs[0, :, 0, 0], float(tr.gamma[0, 0, 0]),
                     float(tr.lam[0, 0, 0]), tr.surf_loss[0, :, 0, :, 0]))
    wall = time.perf_counter() - t0
    rows = sum(len(x) for x, _, _ in st["draws"])
    cvc = ctx.cfg["cv"]
    return {
        "wall_s": wall, "attempted": len(outs), "failed": 0,
        "end_to_end": {"train_rows_per_s": rows / wall},
        "outs": outs,
        "work": {"k": len(st["draws"][0][0]), "d": st["draws"][0][0].shape[1],
                 "slots": len(outs), "n_gamma": 10, "folds": cvc["folds"],
                 "p": 10, "sizes": [len(x) for x, _, _ in st["draws"]]},
        "notes": {"fits": len(outs), "rows": rows,
                  "warm_fit_s": round(st["warm_s"], 6),
                  "fit_s_min": round(min(fit_s), 6),
                  "fit_s_max": round(max(fit_s), 6)},
        "state": st,
    }


def _reference_fits(ctx, st, n_out: int):
    """The window's fits the check compares, drawn from the seed, with the
    reference's own inputs (float64-scaled rows, its own grids, the
    session's fold key for that fit's seed)."""
    rng = np.random.default_rng(st["seed"] % 2**63 + 1)
    pick = rng.choice(n_out, size=min(ctx.traffic["reference_fits"], n_out),
                      replace=False)
    for i in pick:
        x, y, s = st["draws"][i]
        x64 = x.astype(np.float64)
        std = x64.std(0)
        xs = ((x64 - x64.mean(0)) / np.where(std > 0, std, 1.0)).astype(
            np.float32)
        n, d = xs.shape
        msk = np.ones(n, np.float32)
        gam, lams = reference.liquid_grid(n, d, reference.median_dist(xs, msk),
                                          ctx.cfg["cells"]["size"])
        yield int(i), dict(x=xs, y=y, mask=msk, gammas=gam, lambdas=lams,
                           fold_key=_fold_key(s)), n


def check(ctx):
    """The compared numbers, worst over the reference fits."""
    w = ctx.window
    st = w.pop("state")
    outs = w.pop("outs")
    cvc = ctx.cfg["cv"]
    out = {}
    for i, inp, n in _reference_fits(ctx, st, len(outs)):
        c, g, l, surf = outs[i]
        ref = reference.cv_cell(**inp, n_folds=cvc["folds"], tol=cvc["tol"],
                                max_iters=cvc["max_iters"])
        out = worst(out, cv_numbers(ref, c, g, l, surf, inp["gammas"],
                                    inp["lambdas"], n))
    return out


def stand_in(ctx, ref):
    """The record the check reads, with ``ref`` (a copy of the reference at
    a lower precision) in the program's place for the fits it compares."""
    st = _draws(ctx)
    outs = [None] * len(st["draws"])
    cvc = ctx.cfg["cv"]
    for i, inp, _ in _reference_fits(ctx, st, len(outs)):
        r = ref.cv_cell(**inp, n_folds=cvc["folds"], tol=cvc["tol"],
                        max_iters=cvc["max_iters"])
        g, l = r["g_idx"], r["l_idx"]
        outs[i] = (r["coefs"][g, :, l], float(inp["gammas"][g]),
                   float(inp["lambdas"][l]), r["surface"])
    return {"outs": outs, "state": st}


def _fold_key(seed: int):
    """The session's fold key for its single slot: split(PRNGKey(seed))."""
    import jax
    return np.asarray(jax.random.split(jax.random.PRNGKey(seed), 1))[0]
