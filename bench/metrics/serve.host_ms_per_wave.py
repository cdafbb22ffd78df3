"""Mean host milliseconds per serve wave in the window: pack + dispatch +
collect, from ``SVMEngine.stats()["per_stage"]``."""


def read(ctx):
    s = ctx.window.get("serve")
    if not s or s["n_waves"] <= 0:
        return None
    return s["host_ms_per_wave"]
