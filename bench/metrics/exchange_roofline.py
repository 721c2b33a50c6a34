"""The factor exchange's share of its roofline, in %: the least time of one
sweep's exchange at the chip's interconnect peak (``exchange.py``: true rows,
not padded ones; ``ici_peaks.py``) over its device time per sweep (what
``exchange_ms`` reads)."""
import exchange
import ici_peaks


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    s = exchange.exchange_seconds(ctx.trace)
    if s <= 0:
        return None
    import jax
    ici = ici_peaks.ici_peaks_for(jax.devices()[0].device_kind)
    least = exchange.exchange_least_time(ctx.shape, ctx.rank,
                                         ctx.cell.chips, ici)
    return 100.0 * least / (s / ctx.sweeps)
