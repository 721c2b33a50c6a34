"""Seconds from process start to the first timed sweep: JAX start-up,
generation, plan, placement, compilation and the first sweep."""


def read(ctx):
    return ctx.setup_s
