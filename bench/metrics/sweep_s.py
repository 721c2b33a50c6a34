"""Seconds per ALS sweep: the whole window over the sweeps it completed
(host clock, profiler off)."""


def read(ctx):
    return ctx.window_s / ctx.sweeps
