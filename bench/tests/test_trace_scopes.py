"""``program_trace``'s scope join and span-idle sums, checked on a trace
recorded on a TPU v5e with the span tracer on
(``data/fixture_scoped.xplane.pb``, made by ``record_scoped_trace.py``: two
waves of ``train_cells_waves``, the last
one padded, and three serve waves), with the scope tables and span names
saved beside it (``data/fixture_scoped.json``).

Scope seconds are checked against a direct join (each op's module found by
a scan over the module events), span-idle seconds against a sweep over the
op and span boundaries, and the spans' placement against the device's own
module events: the host and device clocks of the capture agree.
"""
import json
import os

import pytest

import program_trace
import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = os.path.join(DATA, "fixture_scoped.xplane.pb")
SIDE = os.path.join(DATA, "fixture_scoped.json")
KERNELS = {"sq_dists": "sq_dists", "gram_epilogue": "gram_from_d2",
           "svm_predict": "predict_cells"}


@pytest.fixture(scope="module")
def trace():
    return program_trace.load(FIXTURE)


@pytest.fixture(scope="module")
def side():
    with open(SIDE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(trace, side):
    return {"busy_s": trace_reduce.reduce(trace, KERNELS)["busy_s"],
            "scope_s": program_trace.scope_s(trace, side["scope_tables"]),
            "span_idle_s": program_trace.span_idle_s(
                trace, set(side["span_names"]))}


def _plane(trace):
    (plane,) = [p for p, ops in trace["device"].items() if ops]
    return plane


def test_scopes_place_the_busy_time(trace, reduced):
    """Every op's self time lands in one scope; little is unscoped."""
    ops = trace["device"][_plane(trace)]
    scope, busy = reduced["scope_s"], reduced["busy_s"]
    total = sum(t for _, t in trace_reduce.self_times(ops)) / 1e9
    assert sum(scope.values()) == pytest.approx(total, rel=1e-9)
    assert scope.get(program_trace.UNSCOPED, 0.0) < 0.05 * busy
    assert scope["cv.solve"] > scope["cv.d2"] > 0
    assert scope["cv.epilogue"] > 0


def test_scope_join_matches_a_direct_join(trace, side, reduced):
    plane = _plane(trace)
    ops, mods = trace["device"][plane], trace["modules"][plane]
    direct = {}
    for (name, a, _), (_, t) in zip(ops, trace_reduce.self_times(ops)):
        module = next((m for m, m0, m1 in mods if m0 <= a <= m1), None)
        table = {}
        if module is not None:
            table = side["scope_tables"].get(module.split("(", 1)[0], {})
        s = table.get(trace_reduce.short_name(name), program_trace.UNSCOPED)
        direct[s] = direct.get(s, 0.0) + t / 1e9
    assert reduced["scope_s"] == pytest.approx(direct, rel=1e-9)


def _sweep_idle_under(ops, spans):
    """ns during which a span runs and no op does, by a boundary sweep."""
    ev = ([(a, 0, 1) for _, a, _ in ops] + [(b, 0, -1) for _, _, b in ops]
          + [(a, 1, 1) for a, _ in spans] + [(b, 1, -1) for _, b in spans])
    ev.sort(key=lambda e: (e[0], -e[2]))
    depth, idle, last = [0, 0], 0.0, None
    for t, kind, d in ev:
        if last is not None and depth[1] > 0 and depth[0] == 0:
            idle += t - last
        depth[kind] += d
        last = t
    return idle


def test_span_idle_matches_a_sweep(trace, side, reduced):
    ops = trace["device"][_plane(trace)]
    names = set(side["span_names"])
    assert {"train.wave.solve", "serve.pack", "serve.collect"} <= names
    for name in names:
        spans = [(a, b) for n, a, b in trace["host"] if n == name]
        assert spans, f"no {name} event on the host plane"
        assert reduced["span_idle_s"][name] == pytest.approx(
            _sweep_idle_under(ops, spans) / 1e9, rel=1e-9, abs=1e-12)


def test_solve_spans_enclose_their_waves(trace):
    spans = sorted((a, b) for n, a, b in trace["host"]
                   if n == "train.wave.solve")
    mods = sorted((a, b) for m, a, b in trace["modules"][_plane(trace)]
                  if m.startswith("jit_train_cells("))
    assert len(spans) == len(mods) == 2
    for (s0, s1), (m0, m1) in zip(spans, mods):
        assert s0 <= m0 and m1 <= s1


def test_load_keeps_the_reduction(trace):
    """The module events ride beside the planes and lines that
    ``trace_reduce`` reads, and leave its reduction as it was."""
    plain = trace_reduce.reduce(trace_reduce.load(FIXTURE), KERNELS)
    assert trace_reduce.reduce(trace, KERNELS) == plain
    assert program_trace.scope_s(trace, None) == pytest.approx(
        {program_trace.UNSCOPED: sum(
            t for _, t in trace_reduce.self_times(
                trace["device"][_plane(trace)])) / 1e9})
    assert program_trace.span_idle_s(trace, ()) == {}


def test_synthetic_join_and_idle():
    """Ops joined on (module, instruction) whatever the program id; an op
    outside every module is unscoped; idle counted only under the named
    spans."""
    tr = {"device": {"/device:TPU:0": [
              ("%fusion.1 = f32[] fusion()", 10.0, 20.0),
              ("%fusion.1 = f32[] fusion()", 40.0, 45.0),
              ("%copy.2 = f32[] copy()", 60.0, 62.0)]},
          "modules": {"/device:TPU:0": [("jit_f(1)", 5.0, 25.0),
                                        ("jit_g(2)", 35.0, 50.0)]},
          "host": [("s", 0.0, 30.0), ("s", 28.0, 41.0), ("other", 0.0, 99.0)]}
    tables = {"jit_f": {"fusion.1": "cv.solve"},
              "jit_g": {"fusion.1": "cv.d2"}}
    assert program_trace.scope_s(tr, tables) == pytest.approx(
        {"cv.solve": 10e-9, "cv.d2": 5e-9, "unscoped": 2e-9})
    # "s" covers [0, 41): busy [10, 20) and [40, 41) inside it
    assert program_trace.span_idle_s(tr, {"s"}) == pytest.approx(
        {"s": 30e-9})
