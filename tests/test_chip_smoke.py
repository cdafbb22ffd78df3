"""chip_smoke.py off the chip: it refuses the CPU, and its phases' parity
checks hold at a tiny size with the Pallas kernels in interpret mode."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
TINY = dict(n_rows=3000, serve_rows=128, CELL_SIZE=300, MAX_ITERATIONS=50)


def _cpu_env(**extra) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


@pytest.fixture
def chip_smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(ROOT)


def test_refuses_cpu():
    r = subprocess.run([sys.executable, SCRIPT], env=_cpu_env(), cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr, r.stderr
    assert '"ok"' not in r.stdout, r.stdout


def test_phases_pass_parity_in_interpret_mode(chip_smoke, monkeypatch):
    """Steer every ops entry point onto its Pallas kernel, interpreted, so
    the engine takes its fused path and the checks compare kernels."""
    from repro.kernels import runtime
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(runtime, "resolve_interpret", lambda _: True)
    # a tiny fit is held to a loose bound; the script's is for full scale
    monkeypatch.setattr(chip_smoke, "TEST_ERROR_BOUND", 0.25)
    jax.clear_caches()              # no trace made under the CPU branch
    try:
        err = chip_smoke.one_chip(None, chip_smoke.CompileClock(),
                                  require_kernels=False, **TINY)
    finally:
        jax.clear_caches()
    assert 0.0 <= err < 0.25


def test_four_device_path_on_forced_host_devices():
    """``--chips 4``'s comparison on 4 virtual CPU devices."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        cs.four_chips(cs.CompileClock(), n_train=None, **{TINY!r})
        print("FOUR_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "output_devices=4" in r.stdout
    assert "selected_gamma_lambda_differ=0" in r.stdout
    assert "FOUR_OK" in r.stdout
