"""Share of its roofline that the per-gamma kernel epilogue reached: the
least time of every gamma's pass the window's calls needed
(``work.gram_epilogue``, slots x gammas) over the device time of the
epilogue kernel's events."""
import work


def read(ctx):
    t = ctx.reduced["kernel_s"].get("gram_epilogue", 0.0) if ctx.reduced else 0.0
    if t <= 0:
        return None
    w = ctx.window["work"]
    least = w["slots"] * w["n_gamma"] * work.least_s(
        *work.gram_epilogue(w["k"]), ctx.peaks)
    return 100.0 * least / t
