"""Each cell's controls are refused by the cell's check.

    python3 -m pytest -q bench/tests/test_control.py      # on a TPU

On the chip: the program's own lower path (bf16 Gram, bf16 bank) in every
cell, and the plain reference at ``high`` (three bf16 passes) in the
program's place where the cell's numbers separate it from ``highest``
(covtype.train cannot: every one of its cells selects a model whose duals
all sit on their box bounds, see PERF.md).  Off the TPU ``high`` equals
``highest`` and the program's bf16 paths are not the chip's, so those
tests skip; the reference at ``highest`` in the program's place then reads
correct at rehearsal sizes on any backend.
"""
import jax
import pytest

import control

SEED = 2147483671
ON_TPU = jax.devices()[0].platform == "tpu"


@pytest.fixture(scope="module", autouse=True)
def compile_cache():
    if ON_TPU:
        from repro.kernels.runtime import enable_compile_cache
        enable_compile_cache()


@pytest.mark.skipif(not ON_TPU, reason="the controls need the TPU's MXU")
@pytest.mark.parametrize("workload,precision", [
    ("covtype.train", None), ("small2k.train", None),
    ("small2k.train", "high"), ("covtype.serve", None),
    ("covtype.serve", "high")])
def test_control_is_refused(workload, precision):
    got = control.control(workload, SEED, 10.0, precision=precision)
    assert got["refused"], got


@pytest.mark.parametrize("workload", ["small2k.train", "covtype.serve"])
def test_reference_in_program_place_is_correct(workload):
    got = control.control(workload, SEED, 2.0, rehearse=True,
                          precision="highest")
    assert not got["refused"], got
    assert all(c["value"] == 0 for c in got["compared"].values()), got
