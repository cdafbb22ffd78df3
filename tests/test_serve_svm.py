"""Serving subsystem: model bank compaction, cell-routed engine, wave plan.

Contract under test, end to end:

  * the engine's one-launch-per-step batched path is BITWISE equal (f32) to
    looping per-cell ``TrainedSVM.decision_function`` at the same padded
    launch shapes (batching must not change numerics);
  * compaction (zero-row drop + dedup) and the checkpoint round-trip
    preserve decisions — compact -> serialize -> load -> identical;
  * the fused batched Pallas kernel matches the distance-cache oracle;
  * a 3-class OvA model trained with cells serves correct class values
    through the bank (accuracy + agreement with the estimator);
  * ``plan_wave`` chunking/padding/LPT invariants.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from repro.data.synthetic import banana_mc, train_test_split
from repro.distributed.planner import plan_wave
from repro.kernels.svm_predict.ops import svm_predict_cells
from repro.kernels.svm_predict.ref import svm_predict_cells_ref
from repro.core.svm import TrainedSVM, train_select
from repro.core.svm import test_error as svm_test_error
from repro.serve.model_bank import ModelBank, _dedup_rows
from repro.serve.svm_engine import SVMEngine
from repro.tasks.builder import make_tasks
from repro.train.svm_trainer import LiquidSVM, SVMTrainerConfig


def _f32_atol(ref, floor: float) -> float:
    """Tolerance scaled to the decision magnitude, never below ``floor``."""
    # f32: reordering the k-term K @ c sum and the GEMM-form D² under exp
    # moves a decision by tens of ulps of its magnitude, not a fixed amount
    return max(floor, 64 * float(np.finfo(np.float32).eps)
               * float(np.abs(ref).max()))


def _random_bank(seed=0, n_cells=4, k=40, d=6, t_count=2, s_count=3,
                 zero_frac=0.0, **kwargs):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_cells, d)).astype(np.float32) * 4
    sv = (centers[:, None, :] + rng.normal(size=(n_cells, k, d))).astype(np.float32)
    coefs = rng.normal(size=(n_cells, k, t_count, s_count)).astype(np.float32)
    if zero_frac:
        coefs[rng.random((n_cells, k)) < zero_frac] = 0.0
    gamma = rng.uniform(0.5, 3.0, size=(n_cells, t_count, s_count)).astype(np.float32)
    mask = np.ones((n_cells, k), np.float32)
    bank = ModelBank.from_cells(sv, mask, coefs, gamma, centers, **kwargs)
    queries = (centers[rng.integers(0, n_cells, 30)]
               + rng.normal(size=(30, d)) * 0.5).astype(np.float32)
    return bank, queries


class TestWavePlan:
    def test_hot_cell_is_chunked_not_padded(self):
        counts = np.array([3, 100, 0, 5])
        plan = plan_wave(counts, m_pad=8)
        assert plan.n_requests == 108
        hot = plan.slot_cell == 1
        assert hot.sum() == 13            # ceil(100 / 8)
        # each cell's chunks cover its queue exactly, in order
        offs = np.sort(plan.slot_off[hot])
        assert offs[0] == 0 and plan.slot_take[hot].sum() == 100

    def test_lpt_order_and_slot_padding(self):
        plan = plan_wave(np.array([1, 9, 2]), m_pad=4, slot_bucket=4)
        takes = plan.slot_take
        assert (takes[:-1] >= takes[1:]).all()       # largest first
        assert plan.n_slots % 4 == 0
        assert (plan.slot_cell[takes == 0] == -1).all()

    def test_auto_m_pad_ignores_outlier(self):
        counts = np.zeros(50, np.int64)
        counts[:49] = 6
        counts[49] = 500                              # one viral cell
        plan = plan_wave(counts, row_bucket=8)
        assert plan.m_pad <= 16                       # p75 of loads, not max
        assert plan.n_requests == int(counts.sum())
        assert plan.pad_fraction < 0.5

    def test_empty(self):
        plan = plan_wave(np.zeros(4, np.int64))
        assert plan.n_slots == 0 and plan.n_requests == 0


class TestCompaction:
    def test_zero_rows_dropped_decisions_kept(self):
        bank, q = _random_bank(seed=1, zero_frac=0.6, drop_tol=0.0)
        assert int(bank.sv_count.sum()) < bank.raw_sv_total
        full_bank, _ = _random_bank(seed=1, zero_frac=0.6, drop_tol=None,
                                    dedup=False)
        x = jnp.asarray(q[:8])
        for c in range(bank.n_cells):
            got = np.asarray(bank.cell_model(c).decision_function(x))
            ref = np.asarray(full_bank.cell_model(c).decision_function(x))
            np.testing.assert_allclose(got, ref, atol=_f32_atol(ref, 2e-6))

    def test_dedup_merges_duplicate_rows(self):
        rng = np.random.default_rng(3)
        sv = rng.normal(size=(6, 4)).astype(np.float32)
        sv[4] = sv[1]                                  # exact duplicate
        coefs = rng.normal(size=(6, 2)).astype(np.float32)
        out_sv, out_co = _dedup_rows(sv, coefs)
        assert out_sv.shape[0] == 5
        np.testing.assert_array_equal(out_sv[1], sv[1])
        np.testing.assert_allclose(out_co[1], coefs[1] + coefs[4], atol=1e-7)
        # decision values preserved: k(x, u) identical for identical u
        x = rng.normal(size=(3, 4)).astype(np.float32)
        k_full = np.exp(-((x[:, None] - sv[None]) ** 2).sum(-1))
        k_comp = np.exp(-((x[:, None] - out_sv[None]) ** 2).sum(-1))
        np.testing.assert_allclose(k_full @ coefs, k_comp @ out_co, atol=1e-5)

    def test_dedup_noop_is_identity(self):
        rng = np.random.default_rng(4)
        sv = rng.normal(size=(5, 3)).astype(np.float32)
        coefs = rng.normal(size=(5, 2)).astype(np.float32)
        out_sv, out_co = _dedup_rows(sv, coefs)
        assert (out_sv == sv).all() and (out_co == coefs).all()

    def test_checkpoint_roundtrip_identical_decisions(self, tmp_path):
        bank, q = _random_bank(seed=2, zero_frac=0.5, drop_tol=0.0)
        x = jnp.asarray(q[:6])
        before = np.asarray(bank.cell_model(0).decision_function(x))
        bank.save(str(tmp_path))
        loaded = ModelBank.load(str(tmp_path))
        for f in ("sv", "coefs", "gammas", "sv_count", "centers",
                  "feat_mean", "feat_std", "classes", "pairs"):
            np.testing.assert_array_equal(getattr(bank, f), getattr(loaded, f))
        assert (loaded.kernel, loaded.n_tasks, loaded.n_sub) == \
            (bank.kernel, bank.n_tasks, bank.n_sub)
        after = np.asarray(loaded.cell_model(0).decision_function(x))
        np.testing.assert_array_equal(before, after)   # bitwise

    def test_bf16_storage_halves_bytes_keeps_decisions(self, tmp_path):
        bank32, q = _random_bank(seed=5, drop_tol=None, dedup=False)
        bank16, _ = _random_bank(seed=5, drop_tol=None, dedup=False,
                                 dtype="bf16")
        assert bank16.sv.nbytes * 2 == bank32.sv.nbytes
        x = jnp.asarray(q[:8])
        d32 = np.asarray(bank32.cell_model(0).decision_function(x))
        d16 = np.asarray(bank16.cell_model(0).decision_function(x))
        # storage-only downcast: decisions track f32 to bf16 rounding scale
        np.testing.assert_allclose(d16, d32, atol=0.05 * np.abs(d32).max())
        # and the bf16 payload survives the raw-byte checkpoint format
        bank16.save(str(tmp_path))
        loaded = ModelBank.load(str(tmp_path))
        assert str(loaded.sv.dtype) == "bfloat16"
        np.testing.assert_array_equal(
            d16, np.asarray(loaded.cell_model(0).decision_function(x)))


class TestEngineParity:
    def test_batched_step_bitwise_equals_per_cell_decision_function(self):
        bank, q = _random_bank(seed=1, drop_tol=None, dedup=False)
        eng = SVMEngine(bank, fused=False, row_bucket=8)
        dec = eng.predict(q)
        assert eng.counters["steps"] == 1              # one launch drained it
        # reference: per-cell decision_function at the same padded shapes
        xs = (q - bank.feat_mean) / bank.feat_std
        cells = eng.route(xs)
        m_pad = 8
        ref = np.zeros_like(dec)
        for c in np.unique(cells):
            model = bank.cell_model(int(c))
            idx = np.where(cells == c)[0]
            for lo in range(0, len(idx), m_pad):
                chunk = idx[lo:lo + m_pad]
                xp = np.zeros((m_pad, xs.shape[1]), np.float32)
                xp[:len(chunk)] = xs[chunk]
                out = np.asarray(model.decision_function(jnp.asarray(xp)))
                ref[chunk] = out[:len(chunk)]
        np.testing.assert_array_equal(dec, ref)        # bitwise, f32 path

    def test_unpadded_reference_within_f32_tolerance(self):
        """Against per-cell decision_function on the RAW routed subsets the
        match is allclose, not bitwise: XLA retiles reductions per batch
        shape (two direct decision_function calls with different m differ
        the same way)."""
        bank, q = _random_bank(seed=6, drop_tol=None, dedup=False)
        eng = SVMEngine(bank, fused=False)
        dec = eng.predict(q)
        xs = (q - bank.feat_mean) / bank.feat_std
        cells = eng.route(xs)
        for c in np.unique(cells):
            idx = np.where(cells == c)[0]
            ref = np.asarray(bank.cell_model(int(c))
                             .decision_function(jnp.asarray(xs[idx])))
            np.testing.assert_allclose(dec[idx], ref,
                                       atol=_f32_atol(ref, 1e-5))

    def test_fused_pallas_kernel_matches_oracle(self):
        rng = np.random.default_rng(7)
        n_cells, m, k, d, p = 3, 37, 50, 7, 5
        xt = jnp.asarray(rng.normal(size=(n_cells, m, d)), jnp.float32)
        sv = jnp.asarray(rng.normal(size=(n_cells, k, d)), jnp.float32)
        co = jnp.asarray(rng.normal(size=(n_cells, k, p)), jnp.float32)
        g = jnp.asarray(rng.uniform(0.5, 3.0, size=(n_cells, p)), jnp.float32)
        for kind in ("gauss_rbf", "laplacian"):
            got = svm_predict_cells(xt, sv, co, g, kind=kind, force_pallas=True)
            ref = svm_predict_cells_ref(xt, sv, co, g, kind=kind)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-4)

    def test_fused_engine_path_close_to_cached(self):
        bank, q = _random_bank(seed=8, drop_tol=0.0, zero_frac=0.4)
        dec_cached = SVMEngine(bank, fused=False).predict(q)
        dec_fused = SVMEngine(bank, fused=True).predict(q)
        np.testing.assert_allclose(dec_fused, dec_cached, atol=1e-4)


class TestPersistentGram:
    def test_repeat_wave_hits_d2_cache(self):
        bank, q = _random_bank(seed=9)
        eng = SVMEngine(bank, fused=False)
        first = eng.predict(q)
        second = eng.predict(q)                        # same routed batch
        assert eng.counters["d2_misses"] == 1
        assert eng.counters["d2_hits"] == 1
        np.testing.assert_array_equal(first, second)

    def test_sweep_gammas_epilogue_only_replay(self):
        import dataclasses
        bank, q = _random_bank(seed=10)
        eng = SVMEngine(bank, fused=False)
        eng.predict(q)
        misses_before = eng.counters["d2_misses"]
        gammas = np.asarray([0.5, 1.0, 2.0], np.float32)
        sweep = np.asarray(eng.sweep_gammas(gammas))
        assert eng.counters["d2_misses"] == misses_before   # no new cross term
        assert sweep.shape[0] == 3
        # each sweep plane == a full engine pass with that gamma everywhere:
        # every reference decision row must appear in the sweep plane
        uniform = dataclasses.replace(bank,
                                      gammas=np.full_like(bank.gammas, 2.0))
        ref = SVMEngine(uniform, fused=False).predict(q)
        flat = sweep[2].reshape(-1, bank.n_tasks * bank.n_sub)
        for row in ref.reshape(ref.shape[0], -1):
            assert np.any(np.all(np.isclose(flat, row, atol=1e-5), axis=1))

    def test_bf16_cache_dtype_bounds_error_and_halves_bytes(self):
        bank, q = _random_bank(seed=11)
        e32 = SVMEngine(bank, fused=False, cache_dtype="f32")
        e16 = SVMEngine(bank, fused=False, cache_dtype="bf16")
        d32 = e32.predict(q)
        d16 = e16.predict(q)
        assert e16.stats()["cached_d2_bytes"] * 2 == e32.stats()["cached_d2_bytes"]
        # one bf16 rounding of d2 before the exp; coefs amplify by sum|c|
        amp = np.abs(bank.coefs).sum(1).max()
        assert np.abs(d16 - d32).max() <= np.exp(-1.0) * 2.0 ** -8 * amp * 1.05


class TestEndToEnd:
    def test_ova_three_class_bank_serving(self):
        x, y = banana_mc(n=900, n_classes=3, seed=21)
        xtr, ytr, xte, yte = train_test_split(x, y, 0.25, 21)
        est = LiquidSVM(SVMTrainerConfig(scenario="ova", n_folds=3,
                                         max_iters=300, cell_method="voronoi",
                                         cell_size=300)).fit(xtr, ytr)
        bank = est.to_bank()
        assert bank.n_tasks == 3 and len(bank.classes) == 3
        assert int(bank.sv_count.sum()) <= bank.raw_sv_total
        eng = SVMEngine(bank, fused=False)
        pred = eng.predict_label(xte)
        acc = float((pred == yte).mean())
        assert acc > 0.8, acc
        agree = float((pred == est.predict(xte)).mean())
        assert agree > 0.97, agree            # bank serving ≈ estimator path

    def test_bank_cold_start_from_checkpoint(self, tmp_path):
        x, y = banana_mc(n=500, n_classes=3, seed=22)
        xtr, ytr, xte, yte = train_test_split(x, y, 0.3, 22)
        est = LiquidSVM(SVMTrainerConfig(scenario="ova", n_folds=3,
                                         max_iters=200)).fit(xtr, ytr)
        est.to_bank().save(str(tmp_path))
        eng = SVMEngine(ModelBank.load(str(tmp_path)), fused=False)
        pred_cold = eng.predict_label(xte)
        pred_warm = SVMEngine(est.to_bank(), fused=False).predict_label(xte)
        np.testing.assert_array_equal(pred_cold, pred_warm)

    def test_trained_svm_multitask_predict_label(self):
        x, y = banana_mc(n=400, n_classes=3, seed=23)
        xtr, ytr, xte, yte = train_test_split(x, y, 0.3, 23)
        tasks = make_tasks(ytr, "ova")
        model = train_select(jnp.asarray(xtr), jnp.asarray(tasks.labels[0]),
                             y_tasks=jnp.asarray(tasks.labels),
                             task_mask=jnp.asarray(tasks.task_mask))
        pred = model.predict_label(jnp.asarray(xte), scenario="ova",
                                   classes=tasks.classes)
        acc = float((pred == yte).mean())
        assert acc > 0.8, acc
        err = float(svm_test_error(model, xte, yte, task="ova",
                                   classes=tasks.classes))
        assert abs((1.0 - acc) - err) < 1e-6

    def test_trained_svm_ava_predict_label(self):
        x, y = banana_mc(n=400, n_classes=3, seed=24)
        xtr, ytr, xte, yte = train_test_split(x, y, 0.3, 24)
        tasks = make_tasks(ytr, "ava")
        model = train_select(jnp.asarray(xtr), jnp.asarray(tasks.labels[0]),
                             y_tasks=jnp.asarray(tasks.labels),
                             task_mask=jnp.asarray(tasks.task_mask))
        pred = model.predict_label(jnp.asarray(xte), scenario="ava",
                                   classes=tasks.classes, pairs=tasks.pairs)
        assert float((pred == yte).mean()) > 0.8
