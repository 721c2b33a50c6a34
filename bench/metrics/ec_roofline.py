"""The MTTKRP's share of its roofline, in %: the least time of one sweep's
MTTKRPs at the chip's peaks (``roofline.ec_cost``; bandwidth-bound at these
shapes) over the device time under ``ec_local`` per sweep."""
import roofline


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    s = ctx.trace.scope_s.get("ec_local", 0.0)
    if s <= 0:
        return None
    least = roofline.sweep_least_time(ctx.nnz, ctx.shape, ctx.rank,
                                      ctx.peaks, solve=False)
    return 100.0 * least / (s / ctx.sweeps)
