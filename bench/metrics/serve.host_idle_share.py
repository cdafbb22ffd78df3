"""Share of the serving window in which the device sat idle while the
engine's host path ran: device-idle seconds under the program's
``serve.route``, ``serve.pack``, ``serve.dispatch`` and ``serve.collect``
spans (disjoint stages of the serving thread, on the trace's host plane),
over the window."""
import program_trace

SPANS = ("serve.route", "serve.pack", "serve.dispatch", "serve.collect")


def read(ctx):
    pt = program_trace.window(ctx)
    idle = pt["span_idle_s"] if pt and pt["n_devices"] else {}
    if not any(s in idle for s in SPANS):
        return None
    return 100.0 * sum(idle.get(s, 0.0) for s in SPANS) / ctx.window["wall_s"]
