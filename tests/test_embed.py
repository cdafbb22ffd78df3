"""repro.embed: extractor one-compile guarantee, cache identity +
crash-safety, pad-tail containment, streaming labels, and the co-located
EmbedServe accounting contract.

(The full ChunkSource contract conformance for ``EmbeddingSource`` — cold
and warm — lives in ``test_sources_contract.py``; this file covers what
the contract suite can't: jit recompile counting, fingerprint semantics,
cell-plan parity, and the serving wrapper.)
"""
from __future__ import annotations

import os

import jax
import numpy as np
import pytest

from repro.embed import (EmbeddingExtractor, EmbeddingSource, LabeledSource,
                         embed_source, params_digest, resolve_arch)
from repro.embed.source import EmbedCache, EmbedCacheError
from repro.models.layers import init_params
from repro.models.model import build_template
from repro.pipeline.cell_stream import build_cells_stream
from repro.pipeline.dataset import ArraySource, DataSourceError

ARCH = "stablelm-1.6b:smoke"
SEQ = 10
B = 16


@pytest.fixture(scope="module")
def cfg():
    return resolve_arch(ARCH)


@pytest.fixture(scope="module")
def extractor(cfg):
    return EmbeddingExtractor(cfg, batch_size=B, seed=0)


@pytest.fixture(scope="module")
def tokens(cfg):
    return np.random.default_rng(0).integers(
        0, cfg.vocab, size=(103, SEQ)).astype(np.int32)


# ------------------------------------------------------------- extractor
class TestExtractor:
    def test_one_compile_across_ragged_calls(self, cfg):
        """The fixed batch shape is the whole point: full blocks, ragged
        tails and sub-batch calls must all reuse ONE compiled program per
        entry point (forward, pool)."""
        ex = EmbeddingExtractor(cfg, batch_size=8, seed=0)
        rng = np.random.default_rng(1)
        for m in (8, 3, 17, 1, 24):              # full, short, ragged, 1-row
            out = ex(rng.integers(0, cfg.vocab, size=(m, SEQ)))
            assert out.shape == (m, cfg.d_model)
            assert out.dtype == np.float32
        assert ex.compile_count == 1
        assert ex._pool_compiles == 1

    def test_padded_rows_do_not_change_real_rows(self, extractor, tokens):
        """A ragged tail is zero-padded to the batch shape; the pad rows
        are sliced off and the REAL rows' bytes match the same rows
        embedded inside a full block."""
        full = extractor(tokens[:B])             # one full block
        short = extractor(tokens[:5])            # same rows + 11 pad rows
        np.testing.assert_array_equal(short, full[:5])

    def test_pooling_matches_unjitted_reference(self, cfg, tokens):
        from repro.models import model as model_mod
        import jax.numpy as jnp
        for pooling in ("mean", "last"):
            ex = EmbeddingExtractor(cfg, pooling=pooling, batch_size=B,
                                    seed=0)
            got = ex(tokens[:B])
            x = tokens[:B].astype(np.int32)
            pos = jnp.broadcast_to(
                jnp.arange(SEQ, dtype=jnp.int32)[None], (B, SEQ))
            h, _, _ = model_mod.backbone(ex.cfg, ex.params,
                                         jnp.asarray(x), pos)
            h32 = np.asarray(h.astype(jnp.float32))
            want = h32.mean(axis=1) if pooling == "mean" else h32[:, -1]
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_empty_input(self, extractor):
        out = extractor(np.zeros((0, SEQ), np.int32))
        assert out.shape == (0, extractor.dim)

    def test_fingerprint_sensitivity(self, cfg, extractor):
        fp = extractor.fingerprint(SEQ)
        assert extractor.fingerprint(SEQ) == fp          # deterministic
        assert extractor.fingerprint(SEQ + 1) != fp      # seq_len
        other_pool = EmbeddingExtractor(cfg, pooling="last", batch_size=B,
                                        seed=0)
        assert other_pool.fingerprint(SEQ) != fp         # pooling
        other_seed = EmbeddingExtractor(cfg, batch_size=B, seed=1)
        assert other_seed.fingerprint(SEQ) != fp         # params
        # batch size is NOT identity: blocks align to corpus offsets
        other_batch = EmbeddingExtractor(cfg, batch_size=B * 2, seed=0)
        assert other_batch.fingerprint(SEQ) == fp

    def test_params_digest_order_independent(self, cfg):
        params = init_params(build_template(cfg), jax.random.PRNGKey(0))
        flipped = dict(reversed(list(params.items())))
        assert params_digest(params) == params_digest(flipped)


# ----------------------------------------------------------------- cache
class TestEmbedCache:
    def test_write_through_seals_and_replays(self, extractor, tokens,
                                             tmp_path):
        src = EmbeddingSource(tokens, extractor, cache=str(tmp_path))
        assert not src.cache_complete()
        cold = src.materialize()                 # write-through pass
        assert src.cache_complete()
        warm = EmbeddingSource(tokens, extractor, cache=str(tmp_path))
        assert warm.cache_complete()
        np.testing.assert_array_equal(warm.materialize(), cold)

    def test_no_tmp_stragglers_after_write(self, extractor, tokens,
                                           tmp_path):
        """Crash-safe writes: after a clean pass, only complete shards +
        meta.json exist — no ``*.tmp.*`` files a reader could trip on."""
        src = EmbeddingSource(tokens, extractor, cache=str(tmp_path))
        src.materialize()
        cache_dir = src.cache.path
        names = os.listdir(cache_dir)
        assert not [n for n in names if ".tmp." in n], names
        assert "meta.json" in names

    def test_partial_cache_resumes_not_recomputes(self, extractor, tokens,
                                                  tmp_path):
        """A crash mid-pass leaves some shards; the next run reuses them
        byte-for-byte and fills only the holes."""
        s1 = EmbeddingSource(tokens, extractor, cache=str(tmp_path))
        next(iter(s1.iter_chunks(B)))            # compute + persist block 0
        cache_dir = s1.cache.path
        shard0 = os.path.join(cache_dir, "shard_00000.npz")
        before = open(shard0, "rb").read()
        s2 = EmbeddingSource(tokens, extractor, cache=str(tmp_path))
        full = s2.materialize()
        assert s2.cache_complete()
        assert open(shard0, "rb").read() == before
        np.testing.assert_array_equal(
            full, EmbeddingSource(tokens, extractor).materialize())

    def test_fingerprint_mismatch_raises(self, cfg, extractor, tokens,
                                         tmp_path):
        EmbeddingSource(tokens, extractor, cache=str(tmp_path)).materialize()
        other = EmbeddingExtractor(cfg, batch_size=B, seed=7)
        fp_dir = os.path.join(str(tmp_path),
                              extractor.fingerprint(SEQ)[:12])
        with pytest.raises(EmbedCacheError, match="identity"):
            EmbedCache(fp_dir, other.fingerprint(SEQ), n_rows=103,
                       dim=extractor.dim, block=B, seq_len=SEQ)
        # the multi-identity root keeps them apart instead
        s2 = EmbeddingSource(tokens, other, cache=str(tmp_path))
        assert not s2.cache_complete()

    def test_geometry_mismatch_raises(self, extractor, tokens, tmp_path):
        cache = EmbedCache(str(tmp_path / "c"), extractor.fingerprint(SEQ),
                           n_rows=50, dim=extractor.dim, block=B,
                           seq_len=SEQ)
        with pytest.raises(EmbedCacheError, match="geometry"):
            EmbeddingSource(tokens, extractor, cache=cache)

    def test_corrupt_shard_names_file_and_rows(self, extractor, tokens,
                                               tmp_path):
        src = EmbeddingSource(tokens, extractor, cache=str(tmp_path))
        src.materialize()
        shard1 = os.path.join(src.cache.path, "shard_00001.npz")
        with open(shard1, "wb") as f:
            f.write(b"not a zip")
        fresh = EmbedCache(src.cache.path, extractor.fingerprint(SEQ),
                           n_rows=103, dim=extractor.dim, block=B,
                           seq_len=SEQ)
        with pytest.raises(DataSourceError, match=r"shard_00001\.npz"):
            fresh.get(1)


# ------------------------------------------------------- pad-tail / cells
class TestCellPlanParity:
    def test_cell_plan_bitwise_invariant_to_chunk_size(self, extractor,
                                                       tokens):
        """The acceptance bar: cell plans built over an EmbeddingSource
        are bit-identical for ANY chunk size, and identical to the plan
        over the materialized reference — padded rows never leak into
        cell statistics or assignments."""
        ref = EmbeddingSource(tokens, extractor).materialize()
        base = build_cells_stream(ArraySource(ref), cell_size=40,
                                  method="voronoi", seed=0)
        for cs in (7, 16, 50, 1000):
            plan = build_cells_stream(EmbeddingSource(tokens, extractor),
                                      cell_size=40, method="voronoi",
                                      seed=0, chunk_size=cs)
            np.testing.assert_array_equal(plan.indices, base.indices)
            np.testing.assert_array_equal(plan.mask, base.mask)
            np.testing.assert_array_equal(plan.owner, base.owner)
            np.testing.assert_array_equal(plan.centers, base.centers)

    def test_pad_rows_never_surface(self, extractor, tokens):
        """103 rows / block 16 -> a 7-row tail padded with 9 zero
        sequences; no chunking may ever emit more than n_rows rows or a
        row equal to the zero-sequence embedding in the tail position."""
        src = EmbeddingSource(tokens, extractor)
        pad_emb = extractor(np.zeros((1, SEQ), np.int32))[0]
        total = 0
        for _, chunk in src.iter_chunks(9):      # straddles the tail block
            total += chunk.shape[0]
        assert total == 103
        tail = src.gather(np.arange(96, 103))
        assert not np.array_equal(tail[-1], pad_emb)


# ---------------------------------------------------------------- labels
class TestStreamingLabels:
    def test_labeled_source_pairs_and_streams(self, tmp_path):
        x = np.random.default_rng(2).normal(size=(57, 4)).astype(np.float32)
        y = np.where(np.random.default_rng(3).random(57) > .5, 1., -1.)
        # label shards on disk, mirroring x npz shards
        paths = []
        for i, (lo, hi) in enumerate([(0, 20), (20, 21), (21, 57)]):
            p = tmp_path / f"y{i}.npz"
            np.savez(p, y=y[lo:hi])
            paths.append(str(p))
        ls = LabeledSource(x, paths)
        np.testing.assert_array_equal(ls.labels_vector(),
                                      y.astype(np.float32))
        ids = np.asarray([56, 0, 20, 20, 33])
        np.testing.assert_array_equal(ls.gather_labels(ids),
                                      y[ids].astype(np.float32))
        for lo, xc, yc in ls.iter_labeled_chunks(10):
            np.testing.assert_array_equal(xc, x[lo:lo + xc.shape[0]])
            np.testing.assert_array_equal(
                yc, y[lo:lo + xc.shape[0]].astype(np.float32))

    def test_row_mismatch_raises(self):
        x = np.zeros((10, 3), np.float32)
        with pytest.raises(DataSourceError, match="mismatch"):
            LabeledSource(x, np.zeros(9))

    def test_svm_session_streams_labels_from_source(self, extractor,
                                                    tokens):
        """SVM(y=None) over a label-carrying EmbeddingSource: the whole
        train->select->test cycle runs without a caller-held y array."""
        from repro.api.session import SVM
        rng = np.random.default_rng(4)
        y = np.where(rng.random(103) > .5, 1., -1.).astype(np.float32)
        src = EmbeddingSource(tokens, extractor, labels=y)
        sel = SVM(src, FOLDS=2, MAX_ITERATIONS=60, CELL_SIZE=60) \
            .train().select()
        res = sel.test(EmbeddingSource(tokens, extractor), y)
        assert 0.0 <= res.error <= 1.0

    def test_plain_source_with_y_none_raises(self):
        from repro.api.session import SVM
        x = np.zeros((20, 3), np.float32)
        with pytest.raises(ValueError, match="label-carrying"):
            SVM(x).train()

    def test_unlabeled_embedding_source_raises(self, extractor, tokens):
        with pytest.raises(DataSourceError, match="no labels"):
            EmbeddingSource(tokens, extractor).labels_vector()


# ------------------------------------------------------------ embed keys
class TestEmbedKeys:
    def test_split_embed_keys(self):
        from repro.api.config import ConfigError, split_embed_keys
        rest, emb = split_embed_keys(
            {"EMBED_ARCH": ARCH, "EMBED_POOL": "last", "EMBED_BATCH": "8",
             "FOLDS": 3})
        assert rest == {"FOLDS": 3}
        assert emb == {"arch": ARCH, "pooling": "last", "batch_size": 8}
        with pytest.raises(ConfigError, match="EMBED_ARCH"):
            split_embed_keys({"EMBED_POOL": "mean"})

    def test_embed_keys_rejected_by_trainer(self):
        from repro.api.config import ConfigError, apply_keys
        from repro.train.svm_trainer import SVMTrainerConfig
        with pytest.raises(ConfigError, match="embed-stage key"):
            apply_keys(SVMTrainerConfig(), {"EMBED_ARCH": ARCH})

    def test_session_wraps_tokens_via_keys(self, tokens):
        from repro.api.session import SVM
        y = np.where(np.random.default_rng(5).random(103) > .5, 1., -1.)
        sess = SVM(tokens, y, EMBED_ARCH=ARCH, EMBED_BATCH=16,
                   FOLDS=2, MAX_ITERATIONS=40)
        assert isinstance(sess._x, EmbeddingSource)

    def test_scenario_front_end_wraps_tokens(self, tokens):
        from repro.api.scenarios import mcSVM
        y = np.random.default_rng(6).integers(0, 3, size=103)
        sess = mcSVM(tokens, y, EMBED_ARCH=ARCH, EMBED_BATCH=16, FOLDS=2)
        assert isinstance(sess._x, EmbeddingSource)


# ------------------------------------------------------------ EmbedServe
class TestEmbedServe:
    @pytest.fixture(scope="class")
    def served(self, extractor, tokens):
        from repro.api.session import SVM
        from repro.serve import EmbedServe, SVMEngine
        rng = np.random.default_rng(7)
        y = np.where(rng.random(103) > .5, 1., -1.).astype(np.float32)
        src = EmbeddingSource(tokens, extractor, labels=y)
        bank = SVM(src, FOLDS=2, MAX_ITERATIONS=60, CELL_SIZE=60) \
            .train().select().to_bank()
        return EmbedServe(SVMEngine(bank), extractor)

    def test_breakdown_sums_exactly_including_embed(self, served, tokens):
        ids = served.submit_tokens(tokens[:9])
        while served.pending:
            served.step()
        for rid in ids:
            b = served.breakdown(int(rid))
            assert b is not None
            assert b["embed_ms"] > 0.0
            parts = (b["embed_ms"] + b["queue_ms"] + b["pack_ms"]
                     + b["dispatch_ms"] + b["device_ms"] + b["collect_ms"])
            assert parts == pytest.approx(b["total_ms"], abs=1e-6)

    def test_stats_merge_embed_stage(self, served):
        st = served.stats()
        assert "embed" in st["per_stage"]
        emb = st["per_stage"]["embed"]
        assert emb["count"] >= 1 and emb["total_ms"] > 0.0
        # the engine's own stages are still there, untouched
        for s in ("queue", "pack", "dispatch", "device", "collect"):
            assert s in st["per_stage"]

    def test_feature_space_passthrough_has_zero_embed(self, served,
                                                      extractor, tokens):
        emb = extractor(tokens[9:12])
        ids = served.submit(emb)
        while served.pending:
            served.step()
        b = served.breakdown(int(ids[0]))
        assert b["embed_ms"] == 0.0

    def test_run_tokens_serves_all_and_monitor_sees_routing(
            self, served, tokens):
        from repro.serve import HealthMonitor
        mon = HealthMonitor(served.engine, drift_window_s=60.0)
        served.attach_monitor(mon)
        results = served.run_tokens(
            tokens[i:i + 8] for i in range(12, 60, 8))
        assert len(results) == 48
        # the monitor observed embedding-space routing: a drift verdict
        # exists (scores keyed by cell)
        assert mon.health()["drift"] is not None

    def test_run_tokens_collects_ready_waves_bitwise_like_sync(
            self, served, tokens, monkeypatch):
        from repro.serve import svm_engine
        batches = [tokens[i:i + 8] for i in range(60, 92, 8)]
        want = []
        for b in batches:
            ids = served.submit_tokens(b)
            out = served.step()
            want.extend(out[int(i)] for i in ids)
        monkeypatch.setattr(svm_engine, "_is_ready", lambda dec: True)
        base = served.counters["submitted"]
        polled = served.counters["ready_collects"]
        results = served.run_tokens(iter(batches), deadline_ms=0.0)
        # one wave per batch; every wave but the drained last one polled
        assert sorted(results) == list(range(base, base + 32))
        assert served.counters["ready_collects"] - polled == 3
        for j, dec in enumerate(want):
            np.testing.assert_array_equal(results[base + j], dec)

    def test_predict_tokens_matches_engine_on_embeddings(self, served,
                                                         extractor, tokens):
        want = served.engine.predict(extractor(tokens[:5]))
        got = served.predict_tokens(tokens[:5])
        np.testing.assert_array_equal(got, want)

    def test_dim_mismatch_raises(self, served):
        from repro.serve import EmbedServe

        class FakeExtractor:
            dim = 3
            batch_size = 4
        with pytest.raises(ValueError, match="d=3"):
            EmbedServe(served.engine, FakeExtractor())


def test_embed_source_front_door(tokens, tmp_path):
    src = embed_source(tokens, arch=ARCH, batch_size=16,
                       cache_dir=str(tmp_path))
    assert isinstance(src, EmbeddingSource)
    assert src.dim == resolve_arch(ARCH).d_model
    src.materialize()
    warm = embed_source(tokens, arch=ARCH, batch_size=16,
                        cache_dir=str(tmp_path))
    assert warm.cache_complete()
