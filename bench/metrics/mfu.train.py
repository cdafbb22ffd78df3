"""The whole window's share of the chip's peak: the least time of the
work any implementation of the window's CV must do (``work.cv_wave_minimum``
at each cell's real size) over the window's wall time."""
import work


def read(ctx):
    if not ctx.reduced or ctx.reduced["busy_s"] <= 0:
        return None
    w = ctx.window["work"]
    least = sum(work.least_s(*work.cv_wave_minimum(
        n, w["d"], w["n_gamma"], w["folds"], w["p"]), ctx.peaks)
        for n in w["sizes"])
    return 100.0 * least / ctx.window["wall_s"] / ctx.reduced["n_devices"]
