"""Device milliseconds per sweep of the operations under the program's
``ec_local`` scope (the MTTKRP's elementwise computation), averaged over
the cell's chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.scope_s.get("ec_local", 0.0)
    return s / ctx.sweeps * 1e3 if s > 0 else None
