"""Device idle share of the traced training window: 1 - busy / window,
busy being the union of the device's op intervals (trace_reduce)."""


def read(ctx):
    r = ctx.reduced
    if not r or r["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / ctx.window["wall_s"])
