"""The least-squares cell's control is refused by its check.

    python3 -m pytest -q bench/tests/test_control_ls.py      # on a TPU

On the chip the plain reference at DEFAULT precision (one bf16 pass) in
the program's place must fail the cell's limits.  Off the TPU DEFAULT
equals HIGHEST, so that test skips; the reference at ``highest`` in the
program's place then reads correct at rehearsal sizes on any backend.
"""
import jax
import pytest

import control_ls

SEED = 2147483671
ON_TPU = jax.devices()[0].platform == "tpu"


@pytest.mark.skipif(not ON_TPU, reason="the control needs the TPU's MXU")
def test_default_precision_control_is_refused():
    from repro.kernels.runtime import enable_compile_cache
    enable_compile_cache()
    got = control_ls.control("yearmsd-ls.train", SEED, 10.0)
    assert got["refused"], got


def test_reference_in_program_place_is_correct():
    got = control_ls.control("yearmsd-ls.train", SEED, 2.0, rehearse=True,
                             precision="highest")
    assert not got["refused"], got
    # the stand-in stores the reference's float64 coefficients and surface
    # in the program's f32 arrays: one f32 rounding apart
    assert all(c["value"] < 1e-6 for c in got["compared"].values()), got
