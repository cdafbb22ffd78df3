"""Share of the window's wall time inside the program's
``train.wave.stage`` spans (host staging of each wave)."""


def read(ctx):
    t = sum(b - a for name, a, b in ctx.spans if name == "train.wave.stage")
    if t <= 0:
        return None
    return 100.0 * t / ctx.window["wall_s"]
