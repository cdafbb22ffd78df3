"""Property-based conformance suite for the async, overlap-routed engine.

Blended decisions and async interleavings are where silent wrongness
hides, so the engine's serving contract is pinned by randomized
properties rather than a handful of fixed examples:

  * **async ≡ sync** — draining the same request batches through the
    double-buffered begin/finish pipeline (admission while a wave is in
    flight) is BITWISE identical to the strictly synchronous
    submit+step() drain: interleaving admission with device work must
    change neither wave composition nor numerics;
  * **overlap blending** — the engine's 2-cell blended decision equals
    the explicit two-cell reference (per-cell
    ``ModelBank.cell_model().decision_function`` weighted by
    :func:`blend_weights`) to f32 tolerance, and is EXACT (bitwise, at
    the padded launch shapes) when the two cells are equidistant;
  * **conservation** — across arbitrary legal interleavings of
    submit / begin_step / finish_step / step / run, every submitted
    request id is returned exactly once: none dropped, none double-served;
  * **tie-breaking** — ``_top2_chunk``'s documented rule (lowest center
    index wins; shared by the overlap cell builder and the engine's
    router) at exactly-equidistant rows, duplicated centers included;
  * **deadline stepper** — with an injected clock, ``run`` launches a
    partially-filled wave exactly when the oldest queued request crosses
    ``deadline_ms``, and fills trigger without a deadline;
  * **ready collection** — ``run`` collects a wave as soon as its result
    is ready (``collect_ready``), not at the next launch; a wave that is
    not ready is left in flight until the launch collects it, and either
    way the results equal the synchronous drain bitwise.

Strategies draw a seed (plus small structural knobs) and derive the
request interleavings, batch sizes and deadlines from ``np.random``
— this keeps the suite running under ``tests/_hypothesis_compat``'s
fallback on bare interpreters.  Quick profiles run in tier-1; the large
profiles are marked ``slow``.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st
from repro.pipeline.assign import _top2_chunk, nearest_top2, nearest_top2_dists
from repro.serve.model_bank import ModelBank
from repro.serve import svm_engine
from repro.serve.svm_engine import SVMEngine, blend_weights

QUICK_EXAMPLES = 8
SLOW_EXAMPLES = 40

_BANKS: dict = {}


def _bank(seed: int, n_cells: int = 3, t_count: int = 2, s_count: int = 1,
          routing: str = "overlap"):
    """Small bank + clustered query pool (cached: few jit shapes, fast draws)."""
    key = (seed, n_cells, t_count, s_count, routing)
    if key not in _BANKS:
        k, d = 16, 4
        rng = np.random.default_rng(seed)
        centers = rng.normal(size=(n_cells, d)).astype(np.float32) * 4.0
        sv = (centers[:, None, :]
              + rng.normal(size=(n_cells, k, d))).astype(np.float32)
        coefs = rng.normal(size=(n_cells, k, t_count, s_count)).astype(np.float32)
        gamma = rng.uniform(0.5, 3.0,
                            size=(n_cells, t_count, s_count)).astype(np.float32)
        mask = np.ones((n_cells, k), np.float32)
        bank = ModelBank.from_cells(sv, mask, coefs, gamma, centers,
                                    routing=routing)
        pool = (centers[rng.integers(0, n_cells, 64)]
                + rng.normal(size=(64, d)) * 1.5).astype(np.float32)
        _BANKS[key] = (bank, pool)
    return _BANKS[key]


def _batches(rng: np.random.Generator, pool: np.ndarray, n_batches: int):
    """Random request batches drawn (with replacement) from the query pool."""
    out = []
    for _ in range(n_batches):
        m = int(rng.integers(1, 13))
        out.append(pool[rng.integers(0, pool.shape[0], m)])
    return out


def _sync_drain(eng: SVMEngine, batches):
    results = {}
    for b in batches:
        eng.submit(b)
        results.update(eng.step())
    return results


def _async_drain(eng: SVMEngine, batches):
    """Double-buffered pipeline: wave i is in flight while batch i+1 is
    routed and admitted — same per-wave request sets as the sync drain."""
    results = {}
    for i, b in enumerate(batches):
        eng.submit(b)
        if i > 0:
            results.update(eng.finish_step())   # collect wave i-1 ...
        eng.begin_step()                        # ... dispatch wave i
    results.update(eng.finish_step())
    return results


def _assert_same_results(got: dict, want: dict, exact: bool = True):
    assert sorted(got) == sorted(want)
    for rid in want:
        if exact:
            np.testing.assert_array_equal(got[rid], want[rid])
        else:
            np.testing.assert_allclose(got[rid], want[rid], atol=1e-5)


class TestAsyncConformance:
    """(a) async drain is bitwise-identical to the synchronous drain."""

    @settings(max_examples=QUICK_EXAMPLES, deadline=None)
    @given(seed=st.integers(0, 2 ** 20), overlap=st.booleans())
    def test_async_bitwise_equals_sync_drain(self, seed, overlap):
        bank, pool = _bank(seed % 3)
        rng = np.random.default_rng(seed)
        batches = _batches(rng, pool, int(rng.integers(1, 5)))
        sync = _sync_drain(SVMEngine(bank, fused=False, overlap=overlap),
                           batches)
        awf = _async_drain(SVMEngine(bank, fused=False, overlap=overlap),
                           batches)
        _assert_same_results(awf, sync, exact=True)

    @settings(max_examples=QUICK_EXAMPLES, deadline=None)
    @given(seed=st.integers(0, 2 ** 20))
    def test_submit_while_in_flight_is_not_lost_or_reordered(self, seed):
        """Admission DURING an in-flight wave lands in the next wave and
        serves with the same numerics as a fresh engine serving it alone."""
        bank, pool = _bank(seed % 3)
        rng = np.random.default_rng(seed)
        b0, b1 = _batches(rng, pool, 2)
        eng = SVMEngine(bank, fused=False)
        ids0 = eng.submit(b0)
        eng.begin_step()
        ids1 = eng.submit(b1)          # legal mid-flight
        first = eng.finish_step()
        assert set(first) == set(int(i) for i in ids0)
        second = eng.step()
        assert set(second) == set(int(i) for i in ids1)
        # wave composition for b1 matches a fresh sync engine's first wave
        ref_eng = SVMEngine(bank, fused=False)
        ref_ids = ref_eng.submit(b1)
        ref = ref_eng.step()
        for rid, ref_rid in zip(map(int, ids1), map(int, ref_ids)):
            np.testing.assert_array_equal(second[rid], ref[ref_rid])

    @pytest.mark.slow
    @settings(max_examples=SLOW_EXAMPLES, deadline=None)
    @given(seed=st.integers(0, 2 ** 24), overlap=st.booleans())
    def test_async_bitwise_equals_sync_drain_large_profile(self, seed, overlap):
        bank, pool = _bank(seed % 3)
        rng = np.random.default_rng(seed)
        batches = _batches(rng, pool, int(rng.integers(1, 7)))
        sync = _sync_drain(SVMEngine(bank, fused=False, overlap=overlap),
                           batches)
        awf = _async_drain(SVMEngine(bank, fused=False, overlap=overlap),
                           batches)
        _assert_same_results(awf, sync, exact=True)


class TestOverlapBlending:
    """(b) overlap blending equals the explicit two-cell reference."""

    @settings(max_examples=QUICK_EXAMPLES, deadline=None)
    @given(seed=st.integers(0, 2 ** 20))
    def test_blend_matches_two_cell_decision_function(self, seed):
        bank, pool = _bank(seed % 3)
        rng = np.random.default_rng(seed)
        q = pool[rng.integers(0, pool.shape[0], int(rng.integers(2, 16)))]
        eng = SVMEngine(bank, fused=False)
        assert eng.overlap                     # bank records routing=overlap
        dec = eng.predict(q)
        c1, c2, d1, d2 = nearest_top2_dists(q, np.asarray(bank.centers))
        w1, w2 = blend_weights(d1, d2)
        for i in range(q.shape[0]):
            a = np.asarray(bank.cell_model(int(c1[i]))
                           .decision_function(jnp.asarray(q[i:i + 1])))[0]
            b = np.asarray(bank.cell_model(int(c2[i]))
                           .decision_function(jnp.asarray(q[i:i + 1])))[0]
            np.testing.assert_allclose(dec[i], w1[i] * a + w2[i] * b,
                                       atol=1e-5)

    def test_equal_weights_exact(self):
        """Duplicated centers: every query is exactly equidistant, weights
        are exactly (0.5, 0.5), and the blend is BITWISE 0.5*(a + b) of the
        per-cell decisions at the engine's own padded launch shapes."""
        rng = np.random.default_rng(7)
        k, d, p = 16, 4, 2
        center = rng.normal(size=(1, d)).astype(np.float32)
        centers = np.repeat(center, 2, axis=0)          # identical pair
        sv = rng.normal(size=(2, k, d)).astype(np.float32) + center
        coefs = rng.normal(size=(2, k, p, 1)).astype(np.float32)
        gamma = rng.uniform(0.5, 2.0, size=(2, p, 1)).astype(np.float32)
        mask = np.ones((2, k), np.float32)
        bank = ModelBank.from_cells(sv, mask, coefs, gamma, centers,
                                    routing="overlap")
        m = 8                                           # == one padded slot
        q = (center + rng.normal(size=(m, d))).astype(np.float32)

        eng = SVMEngine(bank, fused=False, row_bucket=8)
        dec = eng.predict(q)
        assert eng.counters["steps"] == 1               # both parts, one wave
        c1, c2, d1, d2 = nearest_top2_dists(q, centers)
        assert (d1 == d2).all() and (c1 == 0).all() and (c2 == 1).all()
        w1, w2 = blend_weights(d1, d2)
        assert (w1 == np.float32(0.5)).all() and (w2 == np.float32(0.5)).all()
        # per-cell reference at the SAME padded shape (m == m_pad == 8)
        ref0 = np.asarray(bank.cell_model(0).decision_function(jnp.asarray(q)))
        ref1 = np.asarray(bank.cell_model(1).decision_function(jnp.asarray(q)))
        want = np.float32(0.5) * ref0 + np.float32(0.5) * ref1
        np.testing.assert_array_equal(dec, want)        # bitwise

    def test_nearest_bank_serves_exact_1nn(self):
        """voronoi<5 banks record routing=nearest: the engine must fall
        back to the old single-cell path bitwise, no blending."""
        bank_o, pool = _bank(5, routing="overlap")
        import dataclasses
        bank_n = dataclasses.replace(bank_o, routing="nearest")
        # near-boundary queries: midpoints of center pairs (+ tiny noise),
        # so the second cell's blend weight cannot underflow to zero
        rng = np.random.default_rng(5)
        c = np.asarray(bank_o.centers)
        q = np.concatenate([
            (c[[0]] + c[[1]]) / 2, (c[[1]] + c[[2]]) / 2,
            (c[[0]] + c[[2]]) / 2,
        ]) + rng.normal(size=(3, c.shape[1])).astype(np.float32) * 0.05
        q = q.astype(np.float32)
        eng = SVMEngine(bank_n, fused=False)
        assert not eng.overlap
        dec = eng.predict(q)
        ref = SVMEngine(bank_o, fused=False, overlap=False).predict(q)
        np.testing.assert_array_equal(dec, ref)
        # and it differs from the blended path (the blend is real)
        blended = SVMEngine(bank_o, fused=False).predict(q)
        assert np.abs(blended - dec).max() > 0

    def test_single_cell_bank_falls_back_to_1nn(self):
        bank, pool = _bank(6, n_cells=1, routing="nearest")
        import dataclasses
        eng = SVMEngine(dataclasses.replace(bank, routing="overlap"),
                        fused=False)
        assert not eng.overlap                 # no second center to blend
        dec = eng.predict(pool[:4])
        assert np.isfinite(dec).all()


class TestConservation:
    """(c) no request is ever dropped or double-served."""

    @settings(max_examples=QUICK_EXAMPLES, deadline=None)
    @given(seed=st.integers(0, 2 ** 20), overlap=st.booleans())
    def test_every_request_served_exactly_once(self, seed, overlap):
        bank, pool = _bank(seed % 3)
        rng = np.random.default_rng(seed)
        eng = SVMEngine(bank, fused=False, overlap=overlap)
        submitted: set = set()
        served: list = []
        for _ in range(int(rng.integers(4, 16))):
            op = rng.integers(0, 4)
            if op == 0:                                    # submit a batch
                b = pool[rng.integers(0, pool.shape[0], int(rng.integers(1, 9)))]
                submitted.update(int(i) for i in eng.submit(b))
            elif op == 1 and not eng.in_flight:            # dispatch
                eng.begin_step()
            elif op == 2:                                  # collect
                served.extend(eng.finish_step())
            else:                                          # sync step
                served.extend(eng.step())
        while eng.pending or eng.in_flight:                # drain
            served.extend(eng.step())
        assert len(served) == len(set(served))             # never double-served
        assert set(served) == submitted                    # never dropped
        assert eng.counters["served"] == eng.counters["submitted"]

    @settings(max_examples=QUICK_EXAMPLES, deadline=None)
    @given(seed=st.integers(0, 2 ** 20), deadline_ms=st.floats(1.0, 50.0))
    def test_run_conserves_requests_under_deadlines(self, seed, deadline_ms):
        """The latency-bounded stepper serves everything exactly once for
        any arrival pattern / deadline combination (fake clock)."""
        bank, pool = _bank(seed % 3)
        rng = np.random.default_rng(seed)
        clk = [0.0]
        eng = SVMEngine(bank, fused=False, deadline_ms=deadline_ms,
                        clock=lambda: clk[0])
        n_events = int(rng.integers(3, 12))
        expect = 0

        def traffic():
            nonlocal expect
            for _ in range(n_events):
                clk[0] += float(rng.uniform(0.0, 0.02))    # 0-20 ms per tick
                if rng.random() < 0.7:
                    b = pool[rng.integers(0, pool.shape[0],
                                          int(rng.integers(1, 9)))]
                    expect += b.shape[0]
                    yield b
                else:
                    yield None                             # idle tick

        results = eng.run(traffic())
        assert len(results) == expect
        assert sorted(results) == list(range(expect))      # ids 0..n-1, once
        assert eng.pending == 0 and not eng.in_flight


class TestSwapConservation:
    """Conservation must SPAN a mid-run hot swap: no request dropped or
    double-served, and every response attributable to exactly one bank
    version whose engine would have produced the same decision."""

    @settings(max_examples=QUICK_EXAMPLES, deadline=None)
    @given(seed=st.integers(0, 2 ** 20), overlap=st.booleans())
    def test_swap_mid_run_serves_every_request_exactly_once(self, seed,
                                                           overlap):
        import dataclasses
        bank0, pool = _bank(seed % 3)
        banks = {
            0: bank0,
            1: dataclasses.replace(bank0, coefs=-bank0.coefs, version=1),
            2: dataclasses.replace(bank0, coefs=2.0 * bank0.coefs, version=2),
        }
        rng = np.random.default_rng(seed)
        eng = SVMEngine(bank0, fused=False, overlap=overlap)
        submitted: dict = {}                       # rid -> raw row
        served: dict = {}
        next_v = 1
        for _ in range(int(rng.integers(6, 20))):
            op = rng.integers(0, 5)
            if op == 0:                                    # admit a batch
                b = pool[rng.integers(0, pool.shape[0],
                                      int(rng.integers(1, 9)))]
                for i, rid in enumerate(map(int, eng.submit(b))):
                    submitted[rid] = b[i]
            elif op == 1 and not eng.in_flight:            # dispatch
                eng.begin_step()
            elif op == 2:                                  # collect
                served.update(eng.finish_step())
            elif op == 3 and next_v <= 2:                  # hot swap (legal
                eng.swap_bank(banks[next_v])               # mid-flight too)
                next_v += 1
            else:                                          # sync step
                served.update(eng.step())
        while eng.pending or eng.in_flight:                # drain
            served.update(eng.step())

        assert set(served) == set(submitted)               # exactly once
        assert eng.counters["served"] == eng.counters["submitted"]

        # every response attributed to exactly one bank version, and the
        # per-version counters account for every completion
        by_v: dict = {}
        for rid in served:
            v = eng.served_version[rid]
            assert v in banks
            by_v.setdefault(v, []).append(rid)
        assert sum(eng.counters.get(f"served_v{v}", 0)
                   for v in banks) == len(served)

        # correctness per version: a fresh engine on the attributed bank
        # must reproduce the decision for that request's row
        for v, rids in sorted(by_v.items()):
            ref = SVMEngine(banks[v], fused=False, overlap=overlap)
            want = ref.predict(np.stack([submitted[r] for r in rids]))
            for j, r in enumerate(rids):
                np.testing.assert_allclose(served[r], want[j], atol=1e-5)


class TestTop2TieBreak:
    """Satellite: the documented tie-break at exactly-equidistant rows."""

    def test_duplicated_centers_deterministic_pair(self):
        rng = np.random.default_rng(11)
        c = rng.normal(size=(1, 3)).astype(np.float32)
        centers = np.concatenate([c, c, c + 10.0])         # dup at 0 and 1
        x = (c + rng.normal(size=(9, 3))).astype(np.float32)
        nn1, nn2, d1, d2 = _top2_chunk(x.copy(), centers)
        assert (nn1 == 0).all() and (nn2 == 1).all()       # lowest index wins
        np.testing.assert_array_equal(d1, d2)              # exactly tied
        # chunking cannot change the rule
        s1, s2 = nearest_top2(x, centers, chunk_size=2)
        np.testing.assert_array_equal(s1, nn1)
        np.testing.assert_array_equal(s2, nn2)

    def test_geometrically_equidistant_row(self):
        centers = np.asarray([[-1.0, 0.0], [1.0, 0.0], [0.0, 9.0]],
                             np.float32)
        x = np.asarray([[0.0, 0.5], [0.0, -2.0]], np.float32)  # on the bisector
        nn1, nn2, d1, d2 = nearest_top2_dists(x, centers)
        assert (nn1 == 0).all() and (nn2 == 1).all()
        np.testing.assert_array_equal(d1, d2)
        w1, w2 = blend_weights(d1, d2)
        assert (w1 == np.float32(0.5)).all() and (w2 == np.float32(0.5)).all()

    def test_engine_router_shares_the_assign_code_path(self):
        """route_top2 must agree with pipeline.assign.nearest_top2_dists on
        the bank's own centers — same ids, same distances, same weights."""
        bank, pool = _bank(12)
        eng = SVMEngine(bank, fused=False)
        xs = (pool[:16] - bank.feat_mean) / bank.feat_std
        c1, c2, w1, w2 = eng.route_top2(xs)
        r1, r2, rd1, rd2 = nearest_top2_dists(xs, np.asarray(bank.centers))
        np.testing.assert_array_equal(c1, r1.astype(np.int64))
        np.testing.assert_array_equal(c2, r2.astype(np.int64))
        e1, e2 = blend_weights(rd1, rd2)
        np.testing.assert_array_equal(w1, e1)
        np.testing.assert_array_equal(w2, e2)


class TestDeadlineStepper:
    def test_deadline_forces_partial_launch(self):
        bank, pool = _bank(13)
        clk = [0.0]
        eng = SVMEngine(bank, fused=False, deadline_ms=5.0,
                        clock=lambda: clk[0])

        def traffic():
            yield pool[:3]                     # far below fill_rows
            clk[0] += 0.004
            yield None                         # 4 ms: hold
            assert eng.stats().get("waves", 0) == 0
            clk[0] += 0.002
            yield None                         # 6 ms: deadline launch

        results = eng.run(traffic())
        assert len(results) == 3
        stats = eng.stats()
        assert stats["waves"] == 1
        assert stats["age_ms_max"] >= 5.0
        assert 0.0 < stats["occupancy_mean"] < 1.0
        assert sum(stats["age_hist"]) == eng.counters["served_rows"]

    def test_fill_forces_launch_without_deadline(self):
        bank, pool = _bank(13)
        eng = SVMEngine(bank, fused=False, fill_rows=16)

        def traffic():
            yield pool[:4]
            assert eng.stats().get("waves", 0) == 0    # 4 or 8 rows < 16
            yield pool[4:24]                           # fills

        results = eng.run(traffic())
        assert len(results) == 24
        assert eng.stats()["waves"] >= 1

    def test_wave_stats_schema(self):
        bank, pool = _bank(13)
        eng = SVMEngine(bank, fused=False)
        eng.submit(pool[:10])
        eng.step()
        (w,) = eng.wave_stats
        assert set(w) == {"wave", "n_rows", "n_slots", "m_pad", "occupancy",
                          "oldest_ms", "age_ms_mean", "age_hist",
                          "pack_ms", "dispatch_ms", "held_ms",
                          "device_ms", "collect_ms"}
        assert w["n_rows"] == sum(w["age_hist"])
        assert 0.0 < w["occupancy"] <= 1.0
        assert w["wave"] == 0
        for stage in ("pack_ms", "dispatch_ms", "held_ms", "device_ms",
                      "collect_ms"):
            assert w[stage] >= 0.0

    def test_every_served_response_has_a_breakdown(self):
        bank, pool = _bank(13)
        eng = SVMEngine(bank, fused=False)
        ids = eng.submit(pool[:10])
        results = eng.step()
        assert set(results) == set(int(i) for i in ids)
        for rid in results:
            b = eng.breakdown(rid)
            assert b is not None
            assert set(b) == {"wave", "total_ms", "queue_ms", "pack_ms",
                              "dispatch_ms", "device_ms", "collect_ms"}
            # the decomposition is exact: stages sum to the total
            parts = (b["queue_ms"] + b["pack_ms"] + b["dispatch_ms"]
                     + b["device_ms"] + b["collect_ms"])
            assert parts == pytest.approx(b["total_ms"], abs=1e-6)
        assert eng.breakdown(10 ** 9) is None


class TestReadyCollect:
    """``run`` collects the in-flight wave on the first loop turn after its
    result is ready, instead of holding it until the next launch."""

    @staticmethod
    def _deadline_run(eng, pool, clk, seen):
        """Wave 0 launches at 6 ms, idle ticks at 7-9 ms, wave 1 launches
        at 14 ms; ``seen`` records whether wave 0 was answered before the
        second launch."""
        def traffic():
            yield pool[:3]
            clk[0] = 0.006
            yield None                         # deadline: wave 0 dispatched
            for t in (0.007, 0.008, 0.009):
                clk[0] = t
                yield pool[3:5] if t == 0.008 else None
            seen["before_launch"] = eng.breakdown(0) is not None
            seen["waves_before_launch"] = eng.stats()["waves"]
            clk[0] = 0.014
            yield None                         # deadline: wave 1 dispatched
            clk[0] = 0.015
            yield None
        return eng.run(traffic())

    def test_ready_wave_collected_before_next_launch(self, monkeypatch):
        monkeypatch.setattr(svm_engine, "_is_ready", lambda dec: True)
        bank, pool = _bank(13)
        clk = [0.0]
        eng = SVMEngine(bank, fused=False, deadline_ms=5.0,
                        clock=lambda: clk[0])
        seen = {}
        results = self._deadline_run(eng, pool, clk, seen)
        assert sorted(results) == list(range(5))
        assert seen == {"before_launch": True, "waves_before_launch": 1}
        # wave 0 answered on the first turn after dispatch (7 ms), not at
        # wave 1's launch (14 ms): no next-deadline wait in its total
        for rid in range(3):
            assert eng.breakdown(rid)["total_ms"] == pytest.approx(7.0)
        w0, w1 = list(eng.wave_stats)
        assert w0["held_ms"] == pytest.approx(1.0)
        assert w1["held_ms"] == pytest.approx(1.0)
        assert eng.counters["ready_collects"] == 2

    def test_unready_wave_waits_for_the_next_launch(self, monkeypatch):
        monkeypatch.setattr(svm_engine, "_is_ready", lambda dec: False)
        bank, pool = _bank(13)
        clk = [0.0]
        eng = SVMEngine(bank, fused=False, deadline_ms=5.0,
                        clock=lambda: clk[0])
        seen = {}
        results = self._deadline_run(eng, pool, clk, seen)
        assert sorted(results) == list(range(5))
        assert seen == {"before_launch": False, "waves_before_launch": 1}
        # the blocking finish_step at wave 1's launch collected wave 0
        for rid in range(3):
            assert eng.breakdown(rid)["total_ms"] == pytest.approx(14.0)
        w0, w1 = list(eng.wave_stats)
        assert w0["held_ms"] == pytest.approx(8.0)
        assert w1["held_ms"] == pytest.approx(1.0)     # the drain
        assert eng.counters["ready_collects"] == 0

    @settings(max_examples=QUICK_EXAMPLES, deadline=None)
    @given(seed=st.integers(0, 2 ** 20), overlap=st.booleans(),
           ready=st.sampled_from(["always", "never", "device"]))
    def test_run_bitwise_equals_sync_drain(self, seed, overlap, ready):
        """One wave per batch (deadline 0): whichever way each wave is
        collected, every id comes back once with the sync drain's bits."""
        bank, pool = _bank(seed % 3)
        batches = _batches(np.random.default_rng(seed), pool, 6)
        want = _sync_drain(SVMEngine(bank, fused=False, overlap=overlap),
                           batches)
        eng = SVMEngine(bank, fused=False, overlap=overlap, deadline_ms=0.0)
        hook = {"always": lambda dec: True, "never": lambda dec: False,
                "device": svm_engine._is_ready}[ready]
        orig = svm_engine._is_ready
        svm_engine._is_ready = hook
        try:
            got = eng.run(iter(batches))
        finally:
            svm_engine._is_ready = orig
        _assert_same_results(got, want)
        assert eng.stats()["waves"] == len(batches)
        assert eng.counters["served"] == eng.counters["submitted"]
        if ready == "never":
            assert eng.counters["ready_collects"] == 0

    def test_ready_collects_counts_only_polled_waves(self, monkeypatch):
        monkeypatch.setattr(svm_engine, "_is_ready", lambda dec: True)
        bank, pool = _bank(13)
        eng = SVMEngine(bank, fused=False, deadline_ms=0.0)
        # wave 0 launches on batch 0 and is polled in on batch 1's turn,
        # which launches wave 1; the drain collects wave 1
        results = eng.run(iter([pool[:4], pool[4:8]]))
        assert len(results) == 8
        assert eng.stats()["waves"] == 2
        assert eng.counters["ready_collects"] == 1
        # direct calls: nothing in flight, then a finished wave
        assert eng.collect_ready() == {}
        ids = eng.submit(pool[:2])
        eng.begin_step()
        assert sorted(eng.collect_ready()) == [int(i) for i in ids]
        assert eng.counters["ready_collects"] == 2
        assert not eng.in_flight

    def test_host_results_count_as_ready(self):
        assert svm_engine._is_ready(np.zeros(3, np.float32))
        dec = jnp.ones(3)
        dec.block_until_ready()
        assert svm_engine._is_ready(dec)
