"""Optimizer, checkpointing, fault tolerance, data determinism, compression."""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.data.tokens import TokenPipeline, TokenPipelineConfig
from repro.models import model as model_mod
from repro.models.layers import init_params
from repro.train import checkpoint as ckpt
from repro.train.lm_trainer import Trainer, TrainLoopConfig, make_train_step
from repro.train.optimizer import (OptConfig, adamw_step, init_opt_state,
                                   schedule_lr)


def _quad_params():
    return {"w": jnp.asarray([3.0, -2.0], jnp.float32),
            "b": jnp.asarray([[1.0, 1.0], [1.0, 1.0]], jnp.float32)}


class TestOptimizer:
    def test_adamw_reduces_quadratic(self):
        params = _quad_params()
        cfg = OptConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                        total_steps=100, schedule="constant")
        opt = init_opt_state(params, cfg)

        def loss(p):
            return jnp.sum(p["w"] ** 2) + jnp.sum((p["b"] - 0.5) ** 2)

        l0 = float(loss(params))
        for _ in range(50):
            g = jax.grad(loss)(params)
            params, opt, _ = adamw_step(g, opt, cfg)
        assert float(loss(params)) < 0.1 * l0

    @pytest.mark.parametrize("policy", ["fp32", "bf16_mom", "pure_bf16"])
    def test_policies_dtypes(self, policy):
        params = {"w": jnp.zeros((4, 4), jnp.bfloat16)}
        cfg = OptConfig(policy=policy)
        opt = init_opt_state(params, cfg)
        want_master = jnp.float32 if policy != "pure_bf16" else jnp.bfloat16
        want_mom = jnp.float32 if policy == "fp32" else jnp.bfloat16
        assert opt.master["w"].dtype == want_master
        assert opt.m["w"].dtype == want_mom
        g = {"w": jnp.ones((4, 4), jnp.bfloat16)}
        p2, opt2, _ = adamw_step(g, opt, cfg)
        assert p2["w"].dtype == jnp.bfloat16  # compute dtype preserved

    def test_grad_clip(self):
        params = {"w": jnp.zeros((2,), jnp.float32)}
        cfg = OptConfig(grad_clip=1.0, lr=1.0, warmup_steps=0,
                        schedule="constant", weight_decay=0.0)
        opt = init_opt_state(params, cfg)
        g = {"w": jnp.asarray([300.0, 400.0])}  # norm 500
        _, _, metrics = adamw_step(g, opt, cfg)
        np.testing.assert_allclose(float(metrics["grad_norm"]), 500.0, rtol=1e-5)
        np.testing.assert_allclose(float(metrics["clip_scale"]), 1 / 500.0,
                                   rtol=1e-5)

    def test_warmup_cosine_schedule(self):
        cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                        min_lr_frac=0.1)
        assert float(schedule_lr(cfg, jnp.int32(5))) == pytest.approx(0.5)
        assert float(schedule_lr(cfg, jnp.int32(10))) == pytest.approx(1.0)
        assert float(schedule_lr(cfg, jnp.int32(100))) == pytest.approx(0.1)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
        ckpt.save_checkpoint(str(tmp_path), 7, tree, extra={"note": "x"})
        target = jax.tree.map(jnp.zeros_like, tree)
        restored, step, extra = ckpt.restore_checkpoint(str(tmp_path), target)
        assert step == 7 and extra["note"] == "x"
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(x, y),
                     restored, tree)

    def test_keep_last_gc(self, tmp_path):
        tree = {"a": jnp.zeros((2,))}
        for s in (1, 2, 3, 4):
            ckpt.save_checkpoint(str(tmp_path), s, tree, keep_last=2)
        dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
        assert dirs == ["step_00000003", "step_00000004"]

    def test_structure_mismatch_rejected(self, tmp_path):
        ckpt.save_checkpoint(str(tmp_path), 1, {"a": jnp.zeros((2,))})
        with pytest.raises(ValueError, match="structure mismatch"):
            ckpt.restore_checkpoint(str(tmp_path), {"zzz": jnp.zeros((2,))})

    def test_latest_pointer_fallback(self, tmp_path):
        tree = {"a": jnp.zeros((2,))}
        ckpt.save_checkpoint(str(tmp_path), 3, tree)
        with open(tmp_path / "latest", "w") as f:
            f.write("step_99999999")  # torn pointer
        assert ckpt.latest_step(str(tmp_path)) == 3


class TestTokenPipeline:
    def test_deterministic_replay(self):
        cfg = TokenPipelineConfig(vocab=211, seq_len=16, global_batch=4, seed=3)
        p1, p2 = TokenPipeline(cfg), TokenPipeline(cfg)
        b1, b2 = p1.batch(17), p2.batch(17)
        np.testing.assert_array_equal(b1["inputs"], b2["inputs"])
        b3 = p1.batch(18)
        assert not np.array_equal(b1["inputs"], b3["inputs"])

    def test_labels_are_shifted_inputs(self):
        cfg = TokenPipelineConfig(vocab=97, seq_len=12, global_batch=2)
        b = TokenPipeline(cfg).batch(0)
        np.testing.assert_array_equal(np.asarray(b["labels"])[:, :-1],
                                      np.asarray(b["inputs"])[:, 1:])
        assert float(b["mask"][0, -1]) == 0.0

    def test_embed_kind(self):
        cfg = TokenPipelineConfig(vocab=97, seq_len=8, global_batch=2,
                                  input_kind="embed", d_frontend=32)
        b = TokenPipeline(cfg).batch(0)
        assert b["inputs"].shape == (2, 8, 32)


class TestTrainerFaultTolerance:
    def _mk(self, tmp_path, total=12, every=4):
        spec = get_arch("stablelm-1.6b")
        cfg = spec.smoke
        pipe = TokenPipeline(TokenPipelineConfig(
            vocab=cfg.vocab, seq_len=16, global_batch=4, seed=0))
        opt_cfg = OptConfig(lr=1e-3, warmup_steps=2, total_steps=total)
        loop = TrainLoopConfig(total_steps=total, ckpt_every=every,
                               ckpt_dir=str(tmp_path), log_every=1)
        return Trainer(cfg, opt_cfg, loop, pipe)

    def test_loss_decreases(self, tmp_path):
        t = self._mk(tmp_path, total=30, every=100)
        out = t.run()
        assert out["history"][-1]["loss"] < out["history"][0]["loss"]

    def test_crash_resume_matches_uninterrupted(self, tmp_path):
        """Kill at step 6, restart; final params == one uninterrupted run."""
        t_ref = self._mk(tmp_path / "ref", total=8, every=8)
        ref = t_ref.run()

        t_crash = self._mk(tmp_path / "crash", total=8, every=4)
        with pytest.raises(RuntimeError, match="injected failure"):
            t_crash.run(fail_at=6)
        # restart picks up from the step-4 checkpoint
        out = self._mk(tmp_path / "crash", total=8, every=4).run()
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-5),
            out["params"], ref["params"])

    def test_grad_accum_equivalence(self):
        """accum=2 over batch 8 == accum=1 with the same 8 rows."""
        spec = get_arch("stablelm-1.6b")
        cfg = dataclasses.replace(spec.smoke, dtype=jnp.float32)
        params = init_params(model_mod.build_template(cfg), jax.random.PRNGKey(0))
        pipe = TokenPipeline(TokenPipelineConfig(vocab=cfg.vocab, seq_len=16,
                                                 global_batch=8, seed=1))
        batch = pipe.batch(0)
        ocfg = OptConfig(lr=1e-3, warmup_steps=0, schedule="constant")
        s1 = make_train_step(cfg, ocfg, grad_accum=1)
        s2 = make_train_step(cfg, ocfg, grad_accum=2)
        p1, _, m1 = s1(params, init_opt_state(params, ocfg), batch)
        p2, _, m2 = s2(params, init_opt_state(params, ocfg), batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5), p1, p2)


class TestCompression:
    def test_quantize_roundtrip_error_bounded(self):
        from repro.distributed.compression import dequantize_int8, quantize_int8
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(0, 0.1, (256,)), jnp.float32)
        q, s = quantize_int8(g)
        err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(g))
        assert err.max() <= float(s) / 2 + 1e-9

    def test_error_feedback_reduces_bias(self):
        """Mean EF-compressed gradient over many steps converges to the true
        mean gradient (the EF contract)."""
        from repro.distributed.compression import ef_compress, dequantize_int8
        rng = np.random.default_rng(1)
        g_true = jnp.asarray(rng.normal(0, 1, (64,)), jnp.float32)
        err = jnp.zeros_like(g_true)
        acc = np.zeros(64)
        n = 200
        for _ in range(n):
            q, s, err = ef_compress(g_true, err)
            acc += np.asarray(dequantize_int8(q, s))
        np.testing.assert_allclose(acc / n, np.asarray(g_true), atol=1e-3)

    def test_ef_psum_under_shard_map(self):
        """int8 EF all-reduce across 8 forced host devices == f32 mean."""
        import subprocess, sys, textwrap, os as _os
        script = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.distributed.compression import ef_psum
            mesh = jax.make_mesh((8,), ("pod",))
            rng = np.random.default_rng(0)
            g = jnp.asarray(rng.normal(0, 1, (8, 128)), jnp.float32)
            def body(gl, el):
                out, new_err = ef_psum(gl[0], el[0], "pod")
                return out[None], new_err[None]
            f = jax.jit(jax.shard_map(body, mesh=mesh,
                in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod"))))
            out, err = f(g, jnp.zeros_like(g))
            want = np.mean(np.asarray(g), axis=0)
            got = np.asarray(out)[0]
            assert np.allclose(got, want, atol=2e-2), np.abs(got-want).max()
            # every device returns the same mean
            assert np.allclose(np.asarray(out), np.asarray(out)[0:1], atol=1e-6)
            print("OK")
        """)
        env = dict(_os.environ); env["PYTHONPATH"] = "src"
        env.pop("XLA_FLAGS", None)
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           cwd=_os.path.dirname(_os.path.dirname(
                               _os.path.abspath(__file__))),
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK" in r.stdout
