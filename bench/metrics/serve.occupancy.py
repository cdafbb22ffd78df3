"""Useful rows over launched (padded) rows in the window's serve waves:
the engine's ``served_rows / launched_rows`` counters."""


def read(ctx):
    s = ctx.window.get("serve")
    if not s or s["n_waves"] <= 0:
        return None
    return 100.0 * s["occupancy"]
