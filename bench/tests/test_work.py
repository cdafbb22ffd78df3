"""The work and byte functions reproduce their docstrings' hand-worked
examples, and the peaks table refuses an unknown device."""
import pytest

import work


def test_examples():
    pk = work.peaks("TPU v5 lite")
    assert work.sq_dists(4379, 54) == (2_070_969_228.0, 77_648_428.0)
    assert work.least_s(*work.sq_dists(4379, 54), pk) == pytest.approx(
        94.8e-6, rel=1e-3)
    assert work.gram_epilogue(4379) == (0.0, 153_405_128.0)
    assert work.least_s(*work.gram_epilogue(4379), pk) == pytest.approx(
        187.3e-6, rel=1e-3)
    assert work.svm_predict(8, 1536, 54, 1) == (1_339_392.0, 339_680.0)
    assert work.cv_wave_minimum(2000, 54, 10, 5, 10) == (
        4_432_000_000.0, 1_136_432_000.0)
    assert work.least_s(*work.cv_wave_minimum(2000, 54, 10, 5, 10),
                        pk) == pytest.approx(1.3876e-3, rel=1e-3)


def test_unknown_device():
    with pytest.raises(KeyError):
        work.peaks("cpu")
