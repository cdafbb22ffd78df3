"""The FISTA lane-iteration count against its docstring's worked example."""
import pytest

import fista_work
import work


def test_fista_iter_example():
    pk = work.peaks("TPU v5 lite")
    flops, nbytes = fista_work.fista_iter(4379, 10, 4, 5)
    assert flops == 383_512_820.0
    assert nbytes == pytest.approx(15_340_512.8, rel=1e-12)
    assert work.least_s(flops, nbytes, pk) == pytest.approx(18.73e-6,
                                                            rel=1e-3)
    assert 10 * work.least_s(flops, nbytes, pk) == pytest.approx(
        187.3e-6, rel=1e-3)


def test_fista_iter_memory_bound_at_bf16():
    """Half the bytes at bf16 K; the flops do not depend on the width."""
    f32 = fista_work.fista_iter(4379, 10, 4, 5)
    bf16 = fista_work.fista_iter(4379, 10, 2, 5)
    assert bf16[0] == f32[0] and bf16[1] == pytest.approx(f32[1] / 2)
