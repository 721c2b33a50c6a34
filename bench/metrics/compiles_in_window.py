"""XLA backend compilations inside the traced window (jax.monitoring);
every program should have been compiled or loaded in set-up."""


def read(ctx):
    return ctx.compiles_in_window
