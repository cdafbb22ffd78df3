"""Useful share of the batched FISTA loop's work in the window: the
iterations the real solves needed (``iters``) over the lane-iterations the
device ran (``lane_iters``: per gamma, every (slot, fold) lane runs as long
as the slowest lane on its device), the ``train.fista.*`` counts the
window's ``train.wave.solve`` spans carry."""
import program_trace


def read(ctx):
    c = program_trace.fista_counts(ctx)
    if c.get("lane_iters", 0) <= 0:
        return None
    return 100.0 * c["iters"] / c["lane_iters"]
