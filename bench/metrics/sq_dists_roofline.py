"""Share of its roofline that the D² kernel reached in the window: the
least time of every D² the window's calls needed (``work.sq_dists`` at the
call's shape, one per slot) over the device time of the kernel's events."""
import work


def read(ctx):
    t = ctx.reduced["kernel_s"].get("sq_dists", 0.0) if ctx.reduced else 0.0
    if t <= 0:
        return None
    w = ctx.window["work"]
    least = w["slots"] * work.least_s(*work.sq_dists(w["k"], w["d"]), ctx.peaks)
    return 100.0 * least / t
