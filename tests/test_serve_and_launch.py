"""Serve utilities + launch-layer unit tests (no 512-device requirement)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, all_cells, get_arch
from repro.launch.dryrun import collective_bytes
from repro.models import model as model_mod
from repro.serve.kv_cache import cache_bytes, pad_cache


class TestCollectiveParser:
    def test_parses_hlo_ops(self):
        hlo = """\
ENTRY %main.1 (p0: f32[4]) -> f32[4] {
  %all-reduce.17 = f32[8,1,32,1]{3,2,1,0} all-reduce(%x), channel_id=4
  %all-gather.21 = f32[2048,352]{1,0} all-gather(%y), dimensions={0}
  %ag2 = bf16[16,128]{1,0} all-gather(%z), dimensions={0}
  %fusion = f32[4]{0} fusion(%all-reduce.17), kind=kLoop
  %rs = (f32[4]{0}, f32[4]{0}) reduce-scatter-start(%a, %b)
}
"""
        got = collective_bytes(hlo)
        assert got["all-reduce"] == 8 * 32 * 4
        assert got["all-gather"] == 2048 * 352 * 4 + 16 * 128 * 2
        assert got["reduce-scatter"] == 2 * 4 * 4
        assert got["counts"]["all-reduce"] == 1  # fusion operand NOT counted
        assert got["counts"]["all-gather"] == 2

    def test_while_trip_multiplication(self):
        """Collectives inside a scan body multiply by the recovered trips."""
        hlo = """\
%wide.body (arg: (s32[], f32[16])) -> (s32[], f32[16]) {
  %ar = f32[16]{0} all-reduce(%x), channel_id=1
}

%wide.cond (arg: (s32[], f32[16])) -> pred[] {
  %c = s32[] constant(24)
  %cmp = pred[] compare(%iv, %c), direction=LT
}

ENTRY %main.2 (p0: f32[16]) -> f32[16] {
  %w = (s32[], f32[16]{0}) while(%t), condition=%wide.cond, body=%wide.body
  %ar2 = f32[8]{0} all-reduce(%y), channel_id=2
}
"""
        got = collective_bytes(hlo)
        assert got["all-reduce"] == 24 * 16 * 4 + 8 * 4

    def test_ignores_non_collectives(self):
        hlo = "ENTRY %m (p: f32[4]) -> f32[4] {\n  %dot = f32[4,4]{1,0} dot(%a, %b)\n}\n"
        got = collective_bytes(hlo)
        assert sum(v for k, v in got.items() if k != "counts") == 0


class TestCellMatrix:
    def test_cell_count_matches_design(self):
        """40 nominal cells - 6 long_500k skips - 2 hubert decode skips = 32."""
        cells = all_cells()
        assert len(cells) == 32
        long_runners = [a for a, s in cells if s == "long_500k"]
        assert sorted(long_runners) == ["gemma3-4b", "jamba-v0.1-52b",
                                        "rwkv6-1.6b"]
        hubert = [s for a, s in cells if a == "hubert-xlarge"]
        assert sorted(hubert) == ["prefill_32k", "train_4k"]

    def test_shape_kinds(self):
        spec = get_arch("hubert-xlarge")
        assert spec.shape("prefill_32k").kind == "encode"
        spec = get_arch("gemma3-4b")
        assert spec.shape("long_500k").kind == "decode"
        assert spec.shape("train_4k").kind == "train"

    def test_unknown_shape_raises(self):
        with pytest.raises(KeyError):
            get_arch("stablelm-12b").shape("long_500k")


class TestKVCacheUtils:
    def test_cache_bytes_scales_linearly_for_attn(self):
        cfg = get_arch("stablelm-1.6b").smoke
        b1 = cache_bytes(cfg, batch=2, seq=100)
        b2 = cache_bytes(cfg, batch=2, seq=200)
        assert b2 > 1.9 * b1  # kv dominates, linear in seq

    def test_cache_bytes_constant_for_rwkv(self):
        cfg = get_arch("rwkv6-1.6b").smoke
        b1 = cache_bytes(cfg, batch=2, seq=100)
        b2 = cache_bytes(cfg, batch=2, seq=200000)
        assert b1 == b2  # O(1) state — the long_500k story

    def test_pad_cache_pads_only_kv(self):
        cfg = get_arch("jamba-v0.1-52b").smoke
        cache = model_mod.init_cache(cfg, batch=2, seq=8)
        padded = pad_cache(cfg, cache, 16)
        flat_before = jax.tree_util.tree_flatten_with_path(cache)[0]
        flat_after = jax.tree_util.tree_flatten_with_path(padded)[0]
        for (path_b, leaf_b), (path_a, leaf_a) in zip(flat_before, flat_after):
            name = str(path_b[-1])
            if "'k'" in name or "'v'" in name:
                assert leaf_a.shape[-3] == 16
            else:
                assert leaf_a.shape == leaf_b.shape


class TestMeshHelpers:
    def test_batch_axes(self):
        from repro.launch.mesh import batch_axes
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        assert batch_axes(mesh) == ("data",)

    def test_adapt_config_decode_long(self):
        from types import SimpleNamespace
        from repro.launch.shapes import adapt_config
        # production-shaped mock (adapt_config only reads names/shape)
        mesh = SimpleNamespace(axis_names=("data", "model"),
                               shape={"data": 16, "model": 16})
        arch = get_arch("rwkv6-1.6b")
        cfg = adapt_config(arch, arch.shape("long_500k"), mesh)
        assert cfg.batch_axes == ()             # batch 1 cannot shard 16 ways
        assert cfg.seq_axes == ("data", "model")
        assert not cfg.remat

    def test_adapt_config_decode_batched(self):
        from types import SimpleNamespace
        from repro.launch.shapes import adapt_config
        mesh = SimpleNamespace(axis_names=("data", "model"),
                               shape={"data": 16, "model": 16})
        arch = get_arch("stablelm-12b")
        cfg = adapt_config(arch, arch.shape("decode_32k"), mesh)
        assert cfg.batch_axes == ("data",)      # 128 % 16 == 0
        assert cfg.seq_axes == ("model",)       # flash-decoding over model

    def test_adapt_config_train(self):
        from repro.launch.shapes import adapt_config
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        arch = get_arch("command-r-plus-104b")
        cfg = adapt_config(arch, arch.shape("train_4k"), mesh)
        assert cfg.batch_axes == ("data",)
        assert cfg.shard_activations and cfg.remat

