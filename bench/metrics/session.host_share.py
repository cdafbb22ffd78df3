"""Share of the fits' wall time outside the program's ``train.wave.solve``
spans: scaling, cell plan, staging, select and ``to_bank`` on the host."""


def read(ctx):
    fits = sum(b - a for name, a, b in ctx.spans if name == "bench.fit")
    solve = sum(b - a for name, a, b in ctx.spans
                if name == "train.wave.solve")
    if fits <= 0 or solve <= 0:
        return None
    return 100.0 * (fits - solve) / fits
