"""Device-side observability: FISTA counters, scope tables, profiler spans.

What is pinned here and why:
  * the wave scheduler's ``train.fista.*`` counters are the iteration
    counts of the box-QP solves themselves (re-run here one fold and one
    gamma at a time), count real slots only, and ``lane_iters`` bounds the
    useful iterations from above — the solve-layer metrics read them;
  * ``obs.jaxprof.scope_tables`` maps the solve's ``K @ C`` to ``cv.solve``
    and the distance matrix to ``cv.d2`` — the trace join reads them;
  * an enabled span lands by name on the host plane of a ``jax.profiler``
    capture, and ``-S PROFILE_DIR`` makes the CLI take one.
"""
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import cv as cv_mod
from repro.core import kernel_fns
from repro.core.grids import GridSpec
from repro.core.solvers import base as qp
from repro.distributed import cell_trainer
from repro.kernels import runtime
from repro.obs import jaxprof

K, D, FOLDS = 40, 3, 2
GAMMAS = (0.6, 1.1, 2.3)
LAMBDAS = (0.05, 0.005)


def _cfg(max_iters):
    return cv_mod.CVConfig(n_folds=FOLDS, max_iters=max_iters,
                           keep_surface=True)


def _slots(n_slots, n_real, seed=0):
    """``n_slots`` slots of K rows; the first ``n_real`` hold data, the
    rest are padding (zero masks), the way the planner leaves them."""
    rng = np.random.default_rng(seed)
    x = np.zeros((n_slots, K, D), np.float32)
    y = np.zeros((n_slots, 1, K), np.float32)
    m = np.zeros((n_slots, K), np.float32)
    for s in range(n_real):
        rows = K - 3 * s                       # ragged real sizes
        x[s, :rows] = rng.normal(size=(rows, D))
        y[s, 0, :rows] = np.where(x[s, :rows, 0] + 0.4 * rng.normal(
            size=rows) > 0, 1.0, -1.0)
        m[s, :rows] = 1.0
    gam = np.tile(np.asarray(GAMMAS, np.float32)[None], (n_slots, 1))
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed), n_slots))
    return x, y, y != 0, m, gam, keys


def _run_waves(arrays, wave, cfg):
    x, y, tm, m, gam, keys = arrays
    grid = GridSpec(gammas=jnp.asarray(GAMMAS), lambdas=jnp.asarray(LAMBDAS))
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(grid, cfg, 1)

    def stage(lo, hi):
        return (x[lo:hi], y[lo:hi], tm[lo:hi].astype(np.float32), m[lo:hi],
                gam[lo:hi], keys[lo:hi])

    before = {k: obs.metrics.counter("train.fista." + k).value
              for k in ("solves", "iters", "capped", "lane_iters")}
    out = cell_trainer.train_cells_waves(stage, len(x), wave, lam_c, sub_c,
                                         task_c, cfg, n_lam, n_sub)
    counts = {k: obs.metrics.counter("train.fista." + k).value - v
              for k, v in before.items()}
    return out, counts, (lam_c, sub_c, task_c)


def _direct_iters(arrays, slot, cfg, cols):
    """The slot's box-QP iteration counts, one solve per call: fold by
    fold, gamma by gamma, each warm-started from the previous gamma's
    solution, as ``cv_cell`` chains them."""
    x, y, tm, m, _, keys = (jnp.asarray(a[slot]) for a in arrays)
    lam_c, sub_c, task_c = cols
    val = cv_mod.make_fold_masks(keys, m, FOLDS, cfg.fold_scheme, y[0])
    train = (~val) & (m > 0)[None, :]
    y_cols = y[task_c].T
    colmask = tm.astype(jnp.float32)[task_c].T * m[:, None]
    cg = kernel_fns.CachedGram.build(x, name=cfg.kernel)
    c0 = [jnp.zeros((K, lam_c.shape[0]), jnp.float32)] * FOLDS
    out = []
    for g in GAMMAS:
        k_full = cg.gram(jnp.float32(g), "f32")
        l_est = qp.power_iteration_l(k_full)
        row = []
        for f in range(FOLDS):
            tr = train[f].astype(jnp.float32)[:, None] * colmask
            c0[f], it = cv_mod._solve_columns(
                k_full, y_cols, tr, lam_c, sub_c, jnp.sum(tr, axis=0), cfg,
                c0[f], l_est)
            row.append(int(it))
        out.append(row)
    return np.asarray(out)


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Every ops entry point on its Pallas kernel, interpreted."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(runtime, "resolve_interpret", lambda _: True)


@pytest.mark.parametrize("max_iters", [300, 10])
def test_fista_counters_are_the_solves(pallas_interpret, max_iters):
    cfg = _cfg(max_iters)
    arrays = _slots(n_slots=4, n_real=3)
    out, counts, cols = _run_waves(arrays, wave=2, cfg=cfg)
    assert len(out) == len(cell_trainer.wave_keys(cfg))   # not a wave key
    direct = np.stack([_direct_iters(arrays, s, cfg, cols) for s in range(3)])
    assert counts["solves"] == 3 * len(GAMMAS) * FOLDS    # padding adds none
    assert counts["iters"] == int(direct.sum())
    assert counts["capped"] == int((direct >= max_iters).sum())
    assert counts["lane_iters"] >= counts["iters"]
    if max_iters == 10:                     # every solve stops at the cap
        assert counts["capped"] == counts["solves"]
        assert counts["lane_iters"] == 4 * len(GAMMAS) * FOLDS * 10
    else:
        assert counts["capped"] < counts["solves"]


def test_fista_counts_lanes_per_device():
    """Each device runs its lanes as long as its slowest lane, per gamma."""
    it = np.array([[[5, 9]], [[30, 2]], [[7, 7]], [[1, 1]]])  # (4, 1, 2)
    mask = np.ones((4, 3))
    mask[3] = 0.0                                             # padding slot
    one = cell_trainer.fista_counts(it, mask, max_iters=30)
    assert one == {"solves": 6, "iters": 60, "capped": 1,
                   "lane_iters": 30 * 8}
    two = cell_trainer.fista_counts(it, mask, max_iters=30, n_dev=2)
    assert two["lane_iters"] == 30 * 4 + 7 * 4


def test_counters_land_on_the_solve_span():
    cfg = _cfg(10)
    try:
        obs.tracer.enabled = True
        obs.tracer.clear()
        _, counts, _ = _run_waves(_slots(n_slots=2, n_real=1, seed=3),
                                  wave=2, cfg=cfg)
        (sp,) = [s for s in obs.tracer.spans if s.name == "train.wave.solve"]
        assert {k: sp.attrs["fista_" + k] for k in counts} == counts
    finally:
        obs.reset()


def _cv_args(n=24):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
    y = jnp.asarray(np.where(rng.uniform(size=(1, n)) < .5, -1.,
                             1.).astype(np.float32))
    cfg = cv_mod.CVConfig(n_folds=FOLDS, max_iters=20)
    grid = GridSpec(gammas=jnp.asarray(GAMMAS), lambdas=jnp.asarray(LAMBDAS))
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(grid, cfg, 1)
    args = (x, y, jnp.ones((1, n)), jnp.ones((n,)), grid.gammas, lam_c,
            sub_c, task_c, jax.random.PRNGKey(0))
    return args, cfg, n_lam, n_sub


def test_scope_tables_place_solve_and_d2():
    args, cfg, n_lam, n_sub = _cv_args()
    n, p = args[0].shape[0], args[5].shape[0]
    try:
        jaxprof.note(cv_mod.cv_cell, *args, cfg, n_lam=n_lam, n_sub=n_sub)
        tables = jaxprof.scope_tables()
    finally:
        obs.reset()
    assert list(tables) == ["jit_cv_cell"]
    table = tables["jit_cv_cell"]
    text = cv_mod.cv_cell.lower(*args, cfg, n_lam=n_lam,
                                n_sub=n_sub).compile().as_text()
    kc = d2 = None
    for line in text.splitlines():
        name = line.strip().split(" = ", 1)[0].lstrip("%")
        if (f"f32[{n},{FOLDS * p}]" in line and " dot(" in line
                and "while/body/dot_general" in line):
            kc = name               # K @ C, the folds' columns side by side
        if f"f32[{n},{n}]" in line and " dot(%x" in line:
            d2 = name               # the distance matrix's cross term
    assert kc is not None and d2 is not None
    assert table[kc] == "cv.solve"
    assert table[d2] == "cv.d2"
    assert set(table.values()) == {"cv.d2", "cv.epilogue", "cv.solve"}


class _Compiled:
    """A stand-in jitted entry point whose compiled text is given."""

    def __init__(self, text):
        self.text = text

    def lower(self, *args, **kwargs):
        return self

    def compile(self):
        return self

    def as_text(self):
        return self.text


def test_programs_of_one_name_keep_what_they_agree_on():
    one = ('HloModule jit_f, is_scheduled=true\n'
           '  %a = f32[] add(), metadata={op_name="jit(f)/cv.solve/add"}\n'
           '  %b = f32[] mul(), metadata={op_name="jit(f)/cv.d2/mul"}\n'
           '  ROOT %c = f32[] exp(), metadata={op_name="jit(f)/cv.solve/e"}\n')
    two = ('HloModule jit_f, is_scheduled=true\n'
           '  %a = f32[] add(), metadata={op_name="jit(f)/cv.solve/add"}\n'
           '  %b = f32[] mul(), metadata={op_name="jit(f)/cv.epilogue/m"}\n'
           '  %c = f32[] exp()\n'
           '  ROOT %d = f32[] neg(), metadata={op_name="jit(f)/cv.d2/neg"}\n')
    try:
        jaxprof.note(_Compiled(one))
        jaxprof.note(_Compiled(two))
        tables = jaxprof.scope_tables()
    finally:
        obs.reset()
    assert tables == {"jit_f": {"a": "cv.solve", "d": "cv.d2"}}


def test_scope_of_takes_the_innermost():
    assert jaxprof.scope_of("jit(f)/cv.solve/while/body/dot") == "cv.solve"
    assert jaxprof.scope_of("jit(f)/vmap(cv.epilogue)/exp") == "cv.epilogue"
    assert jaxprof.scope_of("jit(f)/cv.d2/x/cv.solve/add") == "cv.solve"
    assert jaxprof.scope_of("jit(f)/cv.solvers/add") is None
    assert jaxprof.scope_of("jit(f)/dot_general") is None


def _host_names(path):
    pd = jax.profiler.ProfileData.from_file(path)
    return {ev.name for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events}


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    try:
        obs.tracer.enabled = True
        jax.profiler.start_trace(str(tmp_path))
        with obs.tracer.span("test.outer"):
            with obs.tracer.annotate("test.region"):
                jnp.sum(jnp.ones(8)).block_until_ready()
        jax.profiler.stop_trace()
    finally:
        obs.reset()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert {"test.outer", "test.region"} <= _host_names(path)


def test_disabled_annotate_is_the_null_singleton():
    tr = obs.Tracer(enabled=False)
    assert tr.annotate("serve.pack") is obs.NULL_SPAN


def test_profile_dir_captures_the_cli_command(tmp_path, capsys):
    from repro import cli
    rng = np.random.default_rng(2)
    x = rng.normal(size=(120, 3)).astype(np.float32)
    np.save(tmp_path / "x.npy", x)
    np.save(tmp_path / "y.npy", np.where(x[:, 0] > 0, 1, -1))
    prof = tmp_path / "prof"
    try:
        assert cli.main(["train", "--data", str(tmp_path / "x.npy"),
                         "--labels", str(tmp_path / "y.npy"),
                         "--model-dir", str(tmp_path / "m"),
                         "-S", "FOLDS=2", "-S", "MAX_ITERATIONS=20",
                         "-S", "ADAPTIVITY_CONTROL=2",
                         "-S", f"PROFILE_DIR={prof}"]) == 0
        assert not jaxprof.active()          # stopped on exit
    finally:
        obs.reset()
    out = json.loads(capsys.readouterr().out)
    assert out["trace"]["train.wave.solve"]["count"] >= 1
    paths = glob.glob(str(prof / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    names = _host_names(paths[0])
    assert {"session.scale", "session.cells", "train.wave.stage",
            "train.wave.solve"} <= names


def test_step_fires_while_tracing():
    import contextlib
    assert isinstance(jaxprof.step("w", 0), contextlib.nullcontext)
    try:
        obs.tracer.enabled = True
        assert isinstance(jaxprof.step("w", 1),
                          jax.profiler.StepTraceAnnotation)
    finally:
        obs.reset()


def test_traced_engine_notes_its_launch():
    from repro.serve import SVMEngine
    from repro.serve.model_bank import ModelBank
    rng = np.random.default_rng(4)
    bank = ModelBank.from_cells(
        rng.normal(size=(3, 16, D)).astype(np.float32),
        np.ones((3, 16), np.float32),
        rng.normal(size=(3, 16, 1, 1)).astype(np.float32),
        np.full((3, 1, 1), 2.0, np.float32),
        rng.normal(size=(3, D)).astype(np.float32))
    eng = SVMEngine(bank)
    try:
        eng.predict(rng.normal(size=(5, D)).astype(np.float32))
        assert jaxprof.scope_tables() == {}        # tracer off: nothing
        obs.tracer.enabled = True
        eng.predict(rng.normal(size=(5, D)).astype(np.float32))
        tables = jaxprof.scope_tables()
    finally:
        obs.reset()
    assert tables and all(k.startswith(("jit_svm_predict_cells",
                                         "jit__decide_cells"))
                          for k in tables)
