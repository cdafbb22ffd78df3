"""The trace reduction, checked on a small trace recorded on a TPU v5e
(``data/fixture.xplane.pb``, made by ``record_trace.py``: one tiny
training wave and three serve waves).

Busy time is checked against an independent count (a sweep over the
sorted op boundaries), kernel times against a direct sum over the events
that carry the kernel's name, and both against the values the reduction
gave when the fixture was recorded.
"""
import os

import pytest

import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "fixture.xplane.pb")
KERNELS = {"sq_dists": "sq_dists", "gram_epilogue": "gram_from_d2",
           "svm_predict": "predict_cells"}


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load(FIXTURE)


@pytest.fixture(scope="module")
def reduced(trace):
    return trace_reduce.reduce(trace, KERNELS)


def _sweep_busy(ops):
    """Busy ns by a sweep over +1/-1 boundary events."""
    ev = sorted([(a, 1) for _, a, _ in ops] + [(b, -1) for _, _, b in ops],
                key=lambda e: (e[0], -e[1]))
    busy, depth, start = 0.0, 0, 0.0
    for t, d in ev:
        if depth == 0 and d == 1:
            start = t
        depth += d
        if depth == 0:
            busy += t - start
    return busy


def test_recorded_values(reduced):
    """What the reduction gave when the fixture was recorded on the chip."""
    assert reduced["busy_s"] == pytest.approx(0.001166626, rel=1e-9)
    assert reduced["kernel_s"] == pytest.approx(
        {"sq_dists": 3.562e-06, "gram_epilogue": 1.0311e-05,
         "svm_predict": 3.3914e-05}, rel=1e-9)
    assert reduced["device_ops"][0] == ["fusion.125", pytest.approx(
        0.000294248, rel=1e-9)]


def test_one_device_plane(trace):
    planes = [p for p, ops in trace["device"].items() if ops]
    assert len(planes) == 1 and planes[0].startswith("/device:TPU:")


def test_busy_matches_sweep(trace, reduced):
    ops = next(ops for ops in trace["device"].values() if ops)
    assert reduced["busy_s"] == pytest.approx(_sweep_busy(ops) / 1e9,
                                              rel=1e-12)
    span = max(b for _, _, b in ops) - min(a for _, a, _ in ops)
    assert 0 < reduced["busy_s"] <= span / 1e9


def test_kernel_time_is_its_events(trace, reduced):
    """Kernels are leaves: their self time is their events' duration."""
    ops = next(ops for ops in trace["device"].values() if ops)
    for k, pat in KERNELS.items():
        direct = sum(b - a for name, a, b in ops
                     if pat in trace_reduce.short_name(name)) / 1e9
        assert direct > 0, f"no {k} events in the fixture"
        assert reduced["kernel_s"][k] == pytest.approx(direct, rel=1e-12)


def test_self_times_partition_the_busy_time(trace, reduced):
    """Nested loop events: self times add up to the busy time."""
    ops = next(ops for ops in trace["device"].values() if ops)
    total = sum(t for _, t in trace_reduce.self_times(ops)) / 1e9
    assert total == pytest.approx(reduced["busy_s"], rel=1e-9)


def test_top_ops_and_gaps(reduced):
    times = [t for _, t in reduced["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= 10
    gaps = [t for _, t in reduced["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and all(g > 0 for g in gaps)
