"""The glue between the harness's context and ``program_trace``: where the
capture is found, the FISTA counts summed from the window's spans, and the
readers that use them, on the program as it is and on one that records
none of it."""
import importlib.util
import json
import os
import shutil

import pytest

import program_trace
from repro import obs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
SOLVE = ("solve.cap_share", "solve.lane_occupancy", "fista_roofline")


class Ctx:
    def __init__(self, **kw):
        self.window = {"wall_s": 1.0}
        self.cfg = {"cv": {"gram_dtype": "f32"}}
        self.peaks = None
        self.__dict__.update(kw)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tracer():
    obs.tracer.clear()
    obs.tracer.enabled = True
    yield obs.tracer
    obs.tracer.enabled = False
    obs.tracer.clear()


def _wave(tracer, **counts):
    with tracer.span("train.wave.solve") as sp:
        sp.set(wave=0, **{"fista_" + k: v for k, v in counts.items()})


def test_fista_counts_sum_the_window_spans(tracer):
    _wave(tracer, solves=100, iters=41_000, capped=20, lane_iters=90_000)
    _wave(tracer, solves=100, iters=39_000, capped=10, lane_iters=80_000)
    with tracer.span("train.wave.stage"):
        pass
    c = program_trace.fista_counts(Ctx())
    assert c == {"solves": 200, "iters": 80_000, "capped": 30,
                 "lane_iters": 170_000}
    assert reader("solve.cap_share").read(Ctx()) == pytest.approx(15.0)
    assert reader("solve.lane_occupancy").read(Ctx()) == pytest.approx(
        100.0 * 80_000 / 170_000)


def test_spans_without_counts_read_nothing(tracer):
    """A program whose solve spans carry no counts: every solve metric is
    left out, and none raises."""
    with tracer.span("train.wave.solve") as sp:
        sp.set(wave=0, slots=2)
    assert program_trace.fista_counts(Ctx()) == {}
    for name in SOLVE:
        assert reader(name).read(Ctx()) is None


def test_trace_dir_is_the_callers_capture(tmp_path):
    ctx = Ctx()
    assert program_trace.trace_dir(ctx) is None

    def run(ctx, tdir):          # the harness keeps both as locals
        return program_trace.trace_dir(ctx)

    assert run(ctx, str(tmp_path)) == str(tmp_path)
    assert run(Ctx(), str(tmp_path)) is not None
    assert program_trace.trace_dir(Ctx(trace_dir="x")) == "x"
    assert program_trace.window(ctx) is None


def test_window_on_the_scoped_fixture(tmp_path, monkeypatch, capsys):
    """The window's scope and span-idle seconds, read once per context,
    join the program's tables and span names with the capture."""
    shutil.copyfile(os.path.join(DATA, "fixture_scoped.xplane.pb"),
                    tmp_path / "w.xplane.pb")
    with open(os.path.join(DATA, "fixture_scoped.json")) as f:
        side = json.load(f)
    monkeypatch.setattr(obs.jaxprof, "scope_tables",
                        lambda: side["scope_tables"])
    monkeypatch.setattr(obs.tracer, "summary",
                        lambda: dict.fromkeys(side["span_names"], {}))
    ctx = Ctx(trace_dir=str(tmp_path))
    pt = program_trace.window(ctx)
    tr = program_trace.load(str(tmp_path / "w.xplane.pb"))
    assert pt["n_devices"] == 1
    assert pt["scope_s"] == program_trace.scope_s(tr, side["scope_tables"])
    assert pt["span_idle_s"] == program_trace.span_idle_s(
        tr, set(side["span_names"]))
    assert program_trace.window(ctx) is pt
    assert capsys.readouterr().err.count("program trace: ") == 1
    idle = reader("serve.host_idle_share").read(ctx)
    spans = ("serve.route", "serve.pack", "serve.dispatch", "serve.collect")
    assert idle == pytest.approx(100.0 * sum(pt["span_idle_s"][s]
                                             for s in spans))


def test_readers_without_a_capture_read_nothing(monkeypatch):
    """A program without the scope tables (or a window with no capture):
    the trace readers leave their metrics out."""
    monkeypatch.delattr(obs.jaxprof, "scope_tables")
    assert reader("serve.host_idle_share").read(Ctx()) is None
    assert reader("fista_roofline").read(Ctx()) is None


def test_fista_roofline_reads_the_solve_scope(tracer, monkeypatch):
    import fista_work
    import work
    _wave(tracer, solves=100, iters=50_000, capped=0, lane_iters=60_000)
    ctx = Ctx(peaks=work.peaks("TPU v5 lite"),
              program_trace={"scope_s": {"cv.solve": 2.5, "cv.d2": 0.1},
                             "span_idle_s": {}, "n_devices": 1})
    ctx.window["work"] = {"k": 4379, "p": 10, "folds": 5}
    one = work.least_s(*fista_work.fista_iter(4379, 10, 4, 5), ctx.peaks)
    assert reader("fista_roofline").read(ctx) == pytest.approx(
        100.0 * 60_000 * one / 2.5)
    ctx.program_trace = {"scope_s": {"unscoped": 2.6}, "span_idle_s": {},
                         "n_devices": 1}
    assert reader("fista_roofline").read(ctx) is None
