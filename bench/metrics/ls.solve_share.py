"""Share of the device's busy time in the least-squares solve: the device
self time that the program's scope tables place in ``cv.ls_factor`` and
``cv.ls_path`` over the union of the device's op intervals (both averaged
over devices)."""
import program_trace

SCOPES = ("cv.ls_factor", "cv.ls_path")


def read(ctx):
    if not ctx.reduced or ctx.reduced["busy_s"] <= 0:
        return None
    pt = program_trace.window(ctx)
    t = sum(pt["scope_s"].get(s, 0.0) for s in SCOPES) if pt else 0.0
    if t <= 0:
        return None
    return 100.0 * t / ctx.reduced["busy_s"]
