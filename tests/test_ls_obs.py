"""Observability of the least-squares solve: its scopes and its counters.

What is pinned here and why:
  * ``obs.jaxprof.scope_tables`` places the ls solve's ops in
    ``cv.ls_factor`` and ``cv.ls_path``, and a hinge compile has neither:
    the ``ls.*`` metrics read those scopes;
  * ``train.ls.paths`` counts the real slots' lambda paths (slot x gamma x
    fold) and ``train.ls.lane_paths`` what the device ran, padding slots
    included; ``train.ls.factor_rows`` the factored block's side times the
    lane paths, ``train.ls.train_rows`` the real fold training rows in
    them; all ride on the ``train.wave.solve`` span;
  * the two solvers' counters do not cross: an ls wave moves no
    ``train.fista.*`` counter, a hinge wave no ``train.ls.*`` one.
"""
import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import cv as cv_mod
from repro.core.grids import GridSpec
from repro.distributed import cell_trainer
from repro.obs import jaxprof

K, D, FOLDS = 32, 3, 2
GAMMAS = (0.6, 1.1, 2.3)
LAMBDAS = (0.05, 0.005)
FISTA = ("solves", "iters", "capped", "lane_iters")
LS = ("paths", "lane_paths", "factor_rows", "train_rows")


def _cfg(solver):
    return cv_mod.CVConfig(solver=solver, n_folds=FOLDS, max_iters=20,
                           keep_surface=True)


def _slots(n_slots, n_real, solver, seed=0):
    """``n_slots`` slots of K rows; the first ``n_real`` hold data, the
    rest are padding (zero masks)."""
    rng = np.random.default_rng(seed)
    x = np.zeros((n_slots, K, D), np.float32)
    y = np.zeros((n_slots, 1, K), np.float32)
    m = np.zeros((n_slots, K), np.float32)
    for s in range(n_real):
        rows = K - 3 * s
        x[s, :rows] = rng.normal(size=(rows, D))
        t = x[s, :rows, 0] + 0.4 * rng.normal(size=rows)
        y[s, 0, :rows] = np.sign(t) if solver == "hinge" else t
        m[s, :rows] = 1.0
    gam = np.tile(np.asarray(GAMMAS, np.float32)[None], (n_slots, 1))
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed), n_slots))
    return x, y, m[:, None, :], m, gam, keys


def _columns(cfg):
    grid = GridSpec(gammas=jnp.asarray(GAMMAS), lambdas=jnp.asarray(LAMBDAS))
    return cv_mod.grid_columns(grid, cfg, 1)


def _counters():
    return {p + k: obs.metrics.counter(p + k).value
            for p, ks in (("train.fista.", FISTA), ("train.ls.", LS))
            for k in ks}


def _run_waves(arrays, wave, cfg):
    """One ``train_cells_waves`` call; the counters' moves and its spans."""
    lam_c, sub_c, task_c, n_lam, n_sub = _columns(cfg)

    def stage(lo, hi):
        return tuple(a[lo:hi] for a in arrays)

    before = _counters()
    try:
        obs.tracer.enabled = True
        obs.tracer.clear()
        cell_trainer.train_cells_waves(stage, len(arrays[0]), wave, lam_c,
                                       sub_c, task_c, cfg, n_lam, n_sub)
        spans = [s for s in obs.tracer.spans if s.name == "train.wave.solve"]
        moved = {k: v - before[k] for k, v in _counters().items()}
    finally:
        obs.reset()
    return moved, spans


def _tables(cfg, arrays):
    lam_c, sub_c, task_c, n_lam, n_sub = _columns(cfg)
    args = [jnp.asarray(a) for a in arrays]
    try:
        jaxprof.note(cell_trainer.train_cells, *args, lam_c, sub_c, task_c,
                     cfg, n_lam, n_sub)
        return jaxprof.scope_tables()
    finally:
        obs.reset()


def test_ls_scopes_in_the_tables():
    tables = _tables(_cfg("ls"), _slots(2, 2, "ls"))
    scopes = set(tables["jit_train_cells"].values())
    assert {"cv.ls_factor", "cv.ls_path", "cv.solve"} <= scopes


def _scoped_eqns(jaxpr, stack=""):
    """Every equation of a jaxpr with its whole name stack: a sub-jaxpr's
    (map, jit) stacks are relative to the equation that holds it."""
    for eqn in jaxpr.eqns:
        here = "/".join(filter(None, (stack, str(eqn.source_info.name_stack))))
        yield eqn, here
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _scoped_eqns(sub, here)


def test_ls_square_work_is_scoped():
    """Every op of the CV's ls branch on an array of the factored block's
    size or more (the gathers and the fold's masked Gram included) lies in
    ``cv.ls_factor`` or ``cv.ls_path``: work left outside them would be
    missing from ``ls.ms_per_path``."""
    n, p = 16, 4
    n_c = cv_mod.ls_factor_side(n, FOLDS)
    rng = np.random.default_rng(0)
    k_full = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    y_cols = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    train = (jnp.arange(n) < n_c).astype(jnp.float32)[:, None] \
        * jnp.ones((1, p))
    lam_c = jnp.asarray([1.0, 0.1, 0.01, 0.001], jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda k, y, t: cv_mod._solve_columns(
            k, y, t, lam_c, jnp.ones(p), jnp.sum(t, axis=0), _cfg("ls"),
            None, None)[0])(k_full, y_cols, train)
    square = [(e.primitive.name, stack)
              for e, stack in _scoped_eqns(jaxpr.jaxpr)
              if any(np.prod(v.aval.shape) >= n_c * n_c for v in e.outvars)]
    assert {"cholesky", "mul"} <= {name for name, _ in square}
    assert all("cv.ls_factor" in stack or "cv.ls_path" in stack
               for _, stack in square), square


def test_hinge_compile_has_no_ls_scope():
    tables = _tables(_cfg("hinge"), _slots(2, 2, "hinge"))
    scopes = set(tables["jit_train_cells"].values())
    assert "cv.solve" in scopes
    assert not scopes & {"cv.ls_factor", "cv.ls_path"}


def test_ls_counts_real_and_lane_paths():
    mask = np.ones((3, 10))
    mask[1, 7:] = 0.0                                       # 7 real rows
    mask[2] = 0.0                                           # padding slot
    side = cv_mod.ls_factor_side(10, 5)                     # 8
    assert cell_trainer.ls_counts(mask, n_gamma=10, n_folds=5) == {
        "paths": 2 * 10 * 5, "lane_paths": 3 * 10 * 5,
        "factor_rows": side * 3 * 10 * 5,
        "train_rows": 4 * (10 + 7) * 10}


def test_ls_wave_counters_and_span():
    arrays = _slots(4, 3, "ls")
    moved, spans = _run_waves(arrays, wave=2, cfg=_cfg("ls"))
    per_slot = len(GAMMAS) * FOLDS
    assert moved["train.ls.paths"] == 3 * per_slot          # padding adds none
    assert moved["train.ls.lane_paths"] == 4 * per_slot
    # one validation fold per real row: it trains in FOLDS - 1 of them
    real_rows = int(arrays[3].sum())                        # K + K-3 + K-6
    assert moved["train.ls.train_rows"] \
        == (FOLDS - 1) * real_rows * len(GAMMAS)
    assert moved["train.ls.factor_rows"] \
        == cv_mod.ls_factor_side(K, FOLDS) * 4 * per_slot
    for k in ("factor_rows", "train_rows"):
        assert sum(s.attrs["ls_" + k] for s in spans) == moved["train.ls." + k]
    assert all(moved["train.fista." + k] == 0 for k in FISTA)
    assert len(spans) == 2
    assert sum(s.attrs["ls_paths"] for s in spans) == 3 * per_slot
    assert [s.attrs["ls_lane_paths"] for s in spans] == [2 * per_slot] * 2
    assert not any("fista_solves" in s.attrs for s in spans)


def test_hinge_wave_moves_no_ls_counter():
    moved, spans = _run_waves(_slots(2, 1, "hinge", seed=3), wave=2,
                              cfg=_cfg("hinge"))
    assert moved["train.ls.paths"] == moved["train.ls.lane_paths"] == 0
    assert moved["train.fista.solves"] == len(GAMMAS) * FOLDS
    (sp,) = spans
    assert {k: sp.attrs["fista_" + k] for k in FISTA} == {
        k: moved["train.fista." + k] for k in FISTA}
    assert not any(k.startswith("ls_") for k in sp.attrs)
