#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run, one process.

    python3 bench/run.py --workload covtype.train --seed 7 --seconds 30 --trace 0

Everything is found by name.  The cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the traffic names its driver
(``bench/drivers/<driver>.py``); each per-layer metric is a reader
``bench/metrics/<name>.py``.  A new cell of an existing driver is data only.

A run: check the device (a TPU, as many chips as the cell asks for; no
fallback), turn on JAX's persistent compilation cache, make the data from
``--seed``, warm up every shape the window uses (all of that is
``setup_s``), measure for ``--seconds``, read the peak device memory, free
the program's state, then check what the window produced against the plain
reference (``bench/reference.py``).  The numbers compared are printed with
their limits as the last lines on standard error and, under ``compared``,
last in the result: the one JSON line that ends standard output.

``--trace 1`` profiles the window and reports the cell's per-layer metrics
instead of its end-to-end ones.  ``--rehearse`` runs a cell at the tiny
sizes of its files' ``rehearsal`` blocks on any backend and reports no
device metric: it is for checking the harness without the chip.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


class Ctx:
    """What a driver and a metric reader get: the cell's files, the run's
    arguments, and (after the window) what the window recorded."""

    def __init__(self, cell: dict, cfg: dict, traffic: dict, seed: int,
                 seconds: float):
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds = seed, seconds
        self.chips = int(cell["chips"])
        self.window = {}      # the driver's record of the window
        self.reduced = None   # trace_reduce.reduce output (traced runs)
        self.spans = []       # host spans (name, t0, t1) of the window
        self.peaks = None


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_module(path: str):
    name = "bench_" + os.path.splitext(os.path.basename(path))[0].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, rehearse: bool, override: dict | None = None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"error: no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:
        cfg = merged(cfg, cfg.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))
    if override:
        cfg = merged(cfg, override.get("config", {}))
        traffic = merged(traffic, override.get("traffic", {}))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])]
    return cell, cfg, traffic, e2e, layer


class CompileClock:
    """Counts and times JAX compiles (all threads), as ``chip_smoke.py``'s
    listener does."""

    def __init__(self):
        import jax
        self.secs, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
        if event.startswith("/jax/core/compile/"):
            self.secs += duration


def compile_cache(jax) -> str:
    """The program's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``
    or its checkout's fixed ``.jax_cache/``), keeping every program: the
    serve cell's many small launch shapes each compile in under JAX's
    default one-second threshold and would otherwise compile in every run."""
    from repro.kernels.runtime import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def judge(numbers: dict, limits: dict):
    """Each compared number beside its limit, and whether all are within."""
    compared = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    return compared, all(c["value"] <= c["limit"] for c in compared.values())


def run(workload: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, override: dict | None = None) -> dict:
    """One run; returns the result dict (also printed by :func:`main`).
    ``override`` ({"config": {...}, "traffic": {...}}) is merged over the
    cell's files: the control switches the program's lower path on."""
    cell, cfg, traffic, e2e, layer = load_cell(workload, rehearse, override)
    import jax
    dev = device_info(jax)
    if not rehearse and (dev["platform"] != "tpu"
                         or dev["count"] < int(cell["chips"])):
        print(f"error: {workload} needs {cell['chips']} TPU chip(s); JAX "
              f"sees {dev['count']} {dev['platform']} device(s)",
              file=sys.stderr)
        raise SystemExit(2)
    cache = compile_cache(jax)
    clock = CompileClock()
    ctx = Ctx(cell, cfg, traffic, seed, seconds)
    driver = load_module(os.path.join(HERE, "drivers",
                                      traffic["driver"] + ".py"))
    state = driver.setup(ctx)
    compiles0 = clock.count
    tdir = None
    if trace:
        from repro import obs
        obs.tracer.clear()
        obs.tracer.enabled = True
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    setup_s = t0 - T_PROCESS
    ctx.window = driver.window(ctx, state)
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
        obs.tracer.enabled = False
        ctx.spans = [(s.name, s.t0, s.t1) for s in obs.tracer.spans]
    ctx.window.setdefault("wall_s", t1 - t0)
    compiles = clock.count - compiles0
    dev["memory_peak_bytes"] = memory_peak(jax)
    del state
    log = (lambda *a: print(*a, file=sys.stderr, flush=True))
    log(f"window: wall_s={ctx.window['wall_s']:.6f} compiles={compiles} "
        f"setup_s={setup_s:.6f} cache={cache} "
        + " ".join(f"{k}={v}" for k, v in ctx.window.get("notes", {}).items()))

    metrics, breakdown = {}, None
    if trace and not rehearse:
        import trace_reduce
        import work
        ctx.peaks = work.peaks(dev["kind"])
        ctx.reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(tdir)),
            kernels=traffic.get("kernels", {}))
        dev["busy_s"] = ctx.reduced["busy_s"]
        dev["window_s"] = ctx.window["wall_s"]
        breakdown = {"device_ops": ctx.reduced["device_ops"],
                     "idle_gaps": ctx.reduced["idle_gaps"]}
        for m in layer:
            reader = load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"))
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    elif not trace and not rehearse:
        values = dict(ctx.window["end_to_end"], setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    if tdir:
        shutil.rmtree(tdir, ignore_errors=True)

    numbers = driver.check(ctx)
    limits = traffic["limits"]
    for name in sorted(set(numbers) - set(limits)):
        log(f"look {name}: {numbers[name]!r} (not compared)")
    compared, correct = judge(numbers, limits)
    if compiles:
        log(f"error: {compiles} compile(s) inside the window")
    for name, c in compared.items():
        log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    result = {"correct": correct, "attempted": ctx.window["attempted"],
              "failed": ctx.window["failed"], "metrics": metrics,
              "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any backend, no device metrics")
    args = ap.parse_args(argv)
    res = run(args.workload, args.seed, args.seconds, bool(args.trace),
              rehearse=args.rehearse)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
