"""Device milliseconds per sweep of the factor exchange: the operations
under the program's ``factor_exchange`` and ``merge`` scopes (the
all-gather of each mode's owner rows, and the intra-group merge where
r > 1), averaged over the cell's chips, each interval once
(``exchange.exchange_seconds``). A TPU collective waits for its slowest
peer, so the cross-chip imbalance of the EC before it shows here. One chip
runs no exchange and reads nothing."""
import exchange


def read(ctx):
    if ctx.trace is None:
        return None
    s = exchange.exchange_seconds(ctx.trace)
    return s / ctx.sweeps * 1e3 if s > 0 else None
