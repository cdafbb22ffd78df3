"""The least-squares cell's yardstick: its plain reference against float64
numpy, its generator's shape, year range and determinism, its comparison,
and its two solve readers on a context with and without what they read."""
import importlib.util
import os

import numpy as np
import pytest

import data_reg
import reference_ls
from repro import obs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return _module(os.path.join(BENCH, "metrics", name + ".py"),
                   "metric_" + name.replace(".", "_"))


class Ctx:
    def __init__(self, **kw):
        self.window = {"wall_s": 1.0}
        self.reduced = None
        self.__dict__.update(kw)


def _tiny(seed=0, n=40, n_real=36, d=5):
    rng = np.random.default_rng(seed)
    x = np.zeros((n, d), np.float32)
    y = np.zeros(n, np.float32)
    x[:n_real] = rng.normal(size=(n_real, d))
    y[:n_real] = np.sin(x[:n_real, 0]) + 0.3 * rng.normal(size=n_real)
    mask = (np.arange(n) < n_real).astype(np.float32)
    return x, y, mask


def test_reference_ls_matches_float64_solves():
    import jax
    x, y, mask = _tiny()
    gammas = np.array([3.0, 1.5, 0.8])
    lambdas = np.array([1e-1, 1e-2, 1e-3, 1e-4])
    key = np.asarray(jax.random.PRNGKey(3))
    ref = reference_ls.cv_cell(x, y, mask, gammas, lambdas, key, n_folds=5)
    val = reference_ls.fold_masks(jax.numpy.asarray(key),
                                  jax.numpy.asarray(mask), 5)
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    d2 = ((x64[:, None, :] - x64[None, :, :]) ** 2).sum(-1)
    surface = np.zeros((3, 4))
    coefs = np.zeros((3, len(x), 4))
    for g, gamma in enumerate(gammas):
        k = np.exp(-d2 / gamma ** 2)
        for va in val:
            tr = ~va & (mask > 0)
            for j, lam in enumerate(lambdas):
                c = np.linalg.solve(k[np.ix_(tr, tr)]
                                    + lam * tr.sum() * np.eye(tr.sum()),
                                    y64[tr])
                f = k[np.ix_(va, tr)] @ c
                surface[g, j] += np.mean((y64[va] - f) ** 2) / 5
                coefs[g, tr, j] += c / 5
    # f32 against float64 at condition numbers up to ~1e4 (lambda 1e-4)
    np.testing.assert_allclose(ref["surface"], surface, rtol=1e-3)
    scale = np.abs(coefs).max(axis=1, keepdims=True)
    assert np.all(np.abs(ref["coefs"] - coefs) <= 1e-3 * scale)
    assert np.all(ref["coefs"][:, mask == 0, :] == 0.0)
    g, j = np.unravel_index(np.argmin(surface), surface.shape)
    assert (ref["g_idx"], ref["l_idx"]) == (g, j)


def test_year_like_shape_range_and_determinism():
    x, year = data_reg.year_like(n=5000, d=90, seed=4)
    assert x.shape == (5000, 90) and x.dtype == np.float32
    assert year.shape == (5000,) and year.dtype == np.float32
    assert year.min() >= 1922 and year.max() <= 2011
    assert np.all(year == np.round(year))
    assert np.mean(year) < np.median(year)            # skewed to the left
    x2, year2 = data_reg.year_like(n=5000, d=90, seed=4)
    assert np.array_equal(x, x2) and np.array_equal(year, year2)
    x3, _ = data_reg.year_like(n=5000, d=90, seed=5)
    assert not np.array_equal(x, x3)


def test_regression_rows_split_and_centring():
    data = {"n_rows": 10000, "n_features": 90, "n_modes": 8, "n_ridges": 16,
            "noise": 1.0, "year_shape": 1.2, "year_scale": 14.0,
            "geometry_seed": 0, "test_frac": 51630 / 515345}
    xtr, ytr, xte, yte, mean_year = data_reg.regression_rows(data)
    assert len(xte) == round(10000 * data["test_frac"]) == 1002
    assert len(xtr) + len(xte) == 10000
    assert abs(float(np.mean(ytr, dtype=np.float64))) < 1e-3
    assert 1990 < mean_year < 2005
    x, year = data_reg.year_like(n=10000, d=90, seed=0, n_modes=8,
                                 noise=1.0, n_ridges=16, shape=1.2,
                                 scale=14.0)
    assert np.array_equal(xtr, x[:len(xtr)])         # the first rows train
    np.testing.assert_allclose(yte + mean_year, year[len(xtr):], atol=1e-3)


def test_config_split_is_the_data_sets_own():
    import json
    with open(os.path.join(BENCH, "configs", "yearmsd-cells.json")) as f:
        data = json.load(f)["data"]
    n = data["n_rows"]
    assert n - round(n * data["test_frac"]) == 463715


def test_ls_numbers():
    drv = _module(os.path.join(BENCH, "drivers", "train_waves_ls.py"),
                  "drv_train_waves_ls")
    gammas, lambdas = np.array([2.0, 1.0]), np.array([1e-1, 1e-2, 1e-3])
    surf = np.array([[10.0, 8.0, 9.0], [12.0, 11.0, 11.5]])
    coefs = np.zeros((2, 4, 3))
    coefs[0, :, 1] = [1.0, -2.0, 0.5, 0.0]
    ref = {"surface": surf, "coefs": coefs, "g_idx": 0, "l_idx": 1}
    c = coefs[0, :, 1] + np.array([0.0, 0.02, 0.0, 0.0])
    got = drv.ls_numbers(ref, c, 2.0, 1e-2, surf * 1.001, gammas, lambdas)
    assert got["coef_gap"] == pytest.approx(0.01)
    assert got["surface_gap"] == pytest.approx(0.001)
    assert got["select_regret"] == 0.0
    got = drv.ls_numbers(ref, coefs[1, :, 2], 1.0, 1e-3, surf, gammas,
                         lambdas)
    assert got["select_regret"] == pytest.approx(11.5 / 8.0 - 1.0)


def test_nonfinite_counts_every_real_cell():
    """Non-finite numbers in any real slot of the window count, not only
    in the reference's; padding slots and slots past the plan do not."""
    from types import SimpleNamespace
    drv = _module(os.path.join(BENCH, "drivers", "train_waves_ls.py"),
                  "drv_train_waves_ls")
    packed = SimpleNamespace(n_slots=4, order=np.array([0, 1, -1, 2]))
    st = {"packed": packed, "slots": [0, 1, 2, 3, 4]}     # 2 pads, 4 past
    coefs = np.zeros((5, 6, 1, 1))
    gamma, lam = np.ones((5, 1, 1)), np.ones((5, 1, 1))
    surf = np.ones((5, 3, 1, 4, 1))
    assert drv.nonfinite(st, coefs, gamma, lam, surf) == 0
    coefs[2], surf[4] = np.nan, np.inf                  # not real cells
    assert drv.nonfinite(st, coefs, gamma, lam, surf) == 0
    coefs[1, 3], surf[3, 0, 0, 2], lam[0] = np.nan, np.inf, np.nan
    assert drv.nonfinite(st, coefs, gamma, lam, surf) == 3


def test_probe_stops_a_wave_that_holds_an_eigh(monkeypatch):
    """The cell stops before its data is made on a program whose training
    wave holds an eigh, and lets this program's wave through."""
    import jax.numpy as jnp
    import run as harness
    from repro.distributed import cell_trainer
    drv = _module(os.path.join(BENCH, "drivers", "train_waves_ls.py"),
                  "drv_train_waves_ls")
    cell, cfg, traffic, _, _ = harness.load_cell("yearmsd-ls.train", True)
    ctx = harness.Ctx(cell, cfg, traffic, 1, 1.0)
    drv._require_batched_solve(ctx)
    real = cell_trainer.train_cells

    def with_eigh(x, *rest, **kw):
        out = real(x, *rest, **kw)
        s, _ = jnp.linalg.eigh(x[0].T @ x[0])
        return (out[0] + s.sum(),) + tuple(out[1:])

    monkeypatch.setattr(cell_trainer, "train_cells", with_eigh)
    with pytest.raises(SystemExit, match="eigh"):
        drv._require_batched_solve(ctx)


@pytest.fixture
def tracer():
    obs.tracer.clear()
    obs.tracer.enabled = True
    yield obs.tracer
    obs.tracer.enabled = False
    obs.tracer.clear()


def test_ls_readers(tracer):
    for paths in (60, 40):
        with tracer.span("train.wave.solve") as sp:
            sp.set(wave=0, ls_paths=paths, ls_lane_paths=100)
    ctx = Ctx(reduced={"busy_s": 4.0},
              program_trace={"scope_s": {"cv.ls_factor": 2.5,
                                         "cv.ls_path": 0.5, "cv.solve": 0.2},
                             "span_idle_s": {}, "n_devices": 1})
    assert reader("ls.ms_per_path").read(ctx) == pytest.approx(
        1000.0 * 3.0 / 100)
    assert reader("ls.solve_share").read(ctx) == pytest.approx(75.0)


def test_ls_readers_read_nothing_without_scopes_or_counts(tracer,
                                                          monkeypatch):
    """A program without the ls scopes or counters (the parent, or a hinge
    cell): both readers leave their metric out."""
    ctx = Ctx(reduced={"busy_s": 4.0},
              program_trace={"scope_s": {"cv.solve": 3.0},
                             "span_idle_s": {}, "n_devices": 1})
    assert reader("ls.ms_per_path").read(ctx) is None
    assert reader("ls.solve_share").read(ctx) is None
    with tracer.span("train.wave.solve") as sp:
        sp.set(wave=0, ls_paths=10, ls_lane_paths=10)
    monkeypatch.delattr(obs.jaxprof, "scope_tables")
    bare = Ctx(reduced={"busy_s": 4.0})
    assert reader("ls.ms_per_path").read(bare) is None
    assert reader("ls.solve_share").read(bare) is None
