"""What a traced window of the program recorded beyond ``trace_reduce``'s
reduction: device seconds per named scope, device-idle seconds under each
of the program's spans, and the FISTA counts of the window's waves.

The trace's op events carry no scope metadata.  Each op's module is the
``XLA Modules`` event (``jit_train_cells(<program id>)``) that encloses it
on its device plane, and the program's scope tables
(``repro.obs.jaxprof.scope_tables``: {module name: {instruction: scope}})
place (module, instruction) in a ``jax.named_scope``.  The program's spans
sit on the host plane as profiler annotations of the same name, on the
device ops' clock.

The metric readers call :func:`window` and :func:`fista_counts` with the
harness's context; a program without the tables, the annotations or the
counts gives empty results, and the readers then leave their metric out.
"""
from __future__ import annotations

import bisect
import json
import sys

import trace_reduce

MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
FISTA = ("solves", "iters", "capped", "lane_iters")


def load(path: str) -> dict:
    """{"device": {plane: [(op, t0_ns, t1_ns)]}, "modules": {plane:
    [(module, t0_ns, t1_ns)] by start}, "host": [(name, t0_ns, t1_ns)]}:
    ``trace_reduce.load``'s planes and lines, with the device planes'
    module events."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    dev, mods, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            ops, mod = [], []
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    out = ops
                elif line.name == MODULES_LINE:
                    out = mod
                else:
                    continue
                for ev in line.events:
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.end_ns)))
            dev[plane.name] = ops
            mods[plane.name] = sorted(mod, key=lambda m: m[1])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.end_ns)))
    return {"device": dev, "modules": mods, "host": host}


def overlap(xs, ys) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def module_of(modules, t: float) -> str | None:
    """The name of the module event (sorted by start) enclosing time t."""
    i = bisect.bisect_right(modules, t, key=lambda m: m[1]) - 1
    if i >= 0 and t <= modules[i][2]:
        return modules[i][0]
    return None


def scope_table(tables: dict, module: str | None) -> dict:
    """The instruction table of a module event ``name(program id)``."""
    if module is None:
        return {}
    return tables.get(module.split("(", 1)[0], {})


def _planes(tr: dict) -> list:
    return [p for p, ops in tr["device"].items() if ops]


def scope_s(tr: dict, tables: dict | None) -> dict:
    """{scope: self seconds} of the device ops, averaged over the device
    planes that ran anything; ops the tables do not place (or that no
    module event encloses) under ``unscoped``."""
    planes = _planes(tr)
    out = {}
    for plane in planes:
        ops = tr["device"][plane]
        modules = tr.get("modules", {}).get(plane, [])
        for (name, a, _), (_, t) in zip(ops, trace_reduce.self_times(ops)):
            scope = scope_table(tables or {}, module_of(modules, a)).get(
                trace_reduce.short_name(name), UNSCOPED)
            out[scope] = out.get(scope, 0.0) + t
    n = max(len(planes), 1)
    return {k: v / n / 1e9 for k, v in out.items()}


def span_idle_s(tr: dict, span_names) -> dict:
    """{span name: seconds the device sat idle while a host event of that
    name, one of ``span_names``, ran}, averaged over the device planes
    that ran anything."""
    spans = {}
    for name, a, b in tr["host"]:
        if name in span_names:
            spans.setdefault(name, []).append((a, b))
    spans = {k: trace_reduce.union(v) for k, v in spans.items()}
    planes = _planes(tr)
    idle = {k: 0.0 for k in spans}
    for plane in planes:
        busy = trace_reduce.union((a, b) for _, a, b in tr["device"][plane])
        for k, iv in spans.items():
            idle[k] += trace_reduce.length(iv) - overlap(iv, busy)
    n = max(len(planes), 1)
    return {k: v / n / 1e9 for k, v in idle.items()}


def trace_dir(ctx) -> str | None:
    """The directory the window was captured into: ``ctx.trace_dir`` where
    the harness sets it, else the ``tdir`` local of the harness's ``run``
    call that holds this ``ctx`` (it keeps the capture there until its
    readers have run)."""
    d = getattr(ctx, "trace_dir", None)
    if d:
        return d
    f = sys._getframe(1)
    while f is not None:
        loc = f.f_locals
        if loc.get("ctx") is ctx and isinstance(loc.get("tdir"), str):
            return loc["tdir"]
        f = f.f_back
    return None


def window(ctx) -> dict | None:
    """{"scope_s", "span_idle_s", "n_devices"} of the traced window, once
    per ``ctx``; None where no capture can be found, or where the program
    has no scope tables: such a program puts no span on the capture
    either, and reading the capture again would find nothing."""
    if "program_trace" in vars(ctx):
        return ctx.program_trace
    ctx.program_trace = None
    from repro import obs
    tables = getattr(obs.jaxprof, "scope_tables", None)
    tdir = trace_dir(ctx)
    if tdir is None or tables is None:
        return None
    tr = load(trace_reduce.find_xplane(tdir))
    ctx.program_trace = {
        "scope_s": scope_s(tr, tables()),
        "span_idle_s": span_idle_s(tr, set(obs.tracer.summary())),
        "n_devices": len(_planes(tr)),
    }
    print("program trace: " + json.dumps(dict(
        ctx.program_trace, fista=fista_counts(ctx))), file=sys.stderr,
        flush=True)
    return ctx.program_trace


def fista_counts(ctx) -> dict:
    """{solves, iters, capped, lane_iters} summed over the window's
    ``train.wave.solve`` spans (the harness clears the span tracer as the
    window opens and turns it off as it closes); {} where the spans carry
    no counts or the tracer's ring dropped some."""
    from repro import obs
    spans = obs.tracer.spans
    out = dict.fromkeys(FISTA, 0)
    seen = False
    for s in spans:
        if s.name == "train.wave.solve" and s.attrs \
                and "fista_solves" in s.attrs:
            seen = True
            for k in FISTA:
                out[k] += int(s.attrs["fista_" + k])
    if not seen or spans.dropped:
        return {}
    return out
