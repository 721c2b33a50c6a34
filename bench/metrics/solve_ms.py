"""Device milliseconds per sweep of every operation outside the MTTKRP's
scopes (``ec_local``, ``merge``, ``factor_exchange``): the ALS solve, the
Grams, the normalization and the fit, averaged over the cell's chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.scope_s.get("other", 0.0)
    return s / ctx.sweeps * 1e3 if s > 0 else None
