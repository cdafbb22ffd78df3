"""Device milliseconds of the least-squares solve per real lambda path: the
device self time that the program's scope tables place in ``cv.ls_factor``
and ``cv.ls_path`` (summed over devices) over the ``ls_paths`` that the
window's ``train.wave.solve`` spans carry (one per real slot, gamma and
fold: one factorisation and its lambda path, however it is factorised)."""
import program_trace

SCOPES = ("cv.ls_factor", "cv.ls_path")


def ls_paths(ctx) -> int:
    """Real lambda paths of the window's waves; 0 where the spans carry no
    count or the tracer's ring dropped some."""
    from repro import obs
    spans = obs.tracer.spans
    if spans.dropped:
        return 0
    return sum(int(s.attrs["ls_paths"]) for s in spans
               if s.name == "train.wave.solve" and s.attrs
               and "ls_paths" in s.attrs)


def read(ctx):
    paths = ls_paths(ctx)
    if paths <= 0:
        return None
    pt = program_trace.window(ctx)
    t = sum(pt["scope_s"].get(s, 0.0) for s in SCOPES) if pt else 0.0
    if t <= 0:
        return None
    return 1000.0 * t * pt["n_devices"] / paths
