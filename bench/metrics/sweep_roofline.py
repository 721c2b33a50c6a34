"""The whole sweep's share of its roofline, in %: the least time of one
sweep's MTTKRPs and solves at the chip's peaks (``roofline``) over the
traced window's seconds per sweep, idle time included. It bounds what a
faster kernel can claim after another leaves the path."""
import roofline


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or ctx.trace.window_s <= 0:
        return None
    least = roofline.sweep_least_time(ctx.nnz, ctx.shape, ctx.rank,
                                      ctx.peaks, solve=True)
    return 100.0 * least / (ctx.trace.window_s / ctx.sweeps)
