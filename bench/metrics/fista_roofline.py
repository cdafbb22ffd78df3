"""Share of its roofline that the FISTA solve reached: the least time of
every lane-iteration the window's loops ran (``train.fista.lane_iters``,
as the window's ``train.wave.solve`` spans carry it, x
``fista_work.fista_iter`` at the padded k, K at the solve's dtype) over
the device self time of the ops the program's scope tables place in
``cv.solve`` (each device's share, averaged over devices)."""
import fista_work
import program_trace
import work

K_BYTES = {"f32": 4, "bf16": 2}


def read(ctx):
    lanes = program_trace.fista_counts(ctx).get("lane_iters", 0)
    if lanes <= 0:
        return None
    pt = program_trace.window(ctx)
    t = pt["scope_s"].get("cv.solve", 0.0) if pt else 0.0
    if t <= 0:
        return None
    w = ctx.window["work"]
    one = work.least_s(*fista_work.fista_iter(
        w["k"], w["p"], K_BYTES[ctx.cfg["cv"]["gram_dtype"]], w["folds"]),
        ctx.peaks)
    return 100.0 * lanes / pt["n_devices"] * one / t
