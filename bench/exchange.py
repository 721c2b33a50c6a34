"""The factor exchange of a CP-ALS sweep: the bytes it must move, counted
from the shapes whatever implements it, and its device time in a trace.

After mode ``d``'s update every chip must hold the whole new factor: the
``rows_d × rank`` float32 rows, of which it computed the ones it owns. Over
``chips`` chips that own equal shares, each chip must receive at least the
``(chips − 1)/chips`` it does not own:

    bytes per chip per sweep = Σ_d rows_d · rank · 4 · (chips − 1) / chips

``rows_d`` are the tensor's true rows, not the padded ownership layout's:
padding is a cost of the layout, not of the work. ``rank`` is the model's
rank. One chip moves nothing.
"""
from __future__ import annotations

import re

__all__ = ["SCOPES", "exchange_bytes", "exchange_least_time",
           "exchange_seconds"]

SCOPES = ("factor_exchange", "merge")
# a loop's own event spans the events of its body on the device's line
_LOOP_RE = re.compile(r"while(\.\d+)?")


def exchange_bytes(shape, rank: int, chips: int) -> float:
    """Bytes each chip must receive in one sweep's factor exchange."""
    return sum(int(rows) for rows in shape) * rank * 4 * (chips - 1) / chips


def exchange_least_time(shape, rank: int, chips: int, ici: dict) -> float:
    """Least seconds of one sweep's exchange at the chip's ICI peak."""
    return exchange_bytes(shape, rank, chips) / ici["ici_bytes_per_s"]


def exchange_seconds(summary) -> float:
    """Device seconds, mean over chips, of the ops under the exchange's
    scopes in a ``tracing.Summary``. The ring runs its rounds in a loop,
    and the loop op's event holds its body's ops, which are counted
    themselves, so loop ops are left out: each interval counts once."""
    total = 0.0
    for name, secs in summary.op_s.items():
        op, _, scope = name.rpartition(" [")
        if scope[:-1] in SCOPES and \
                not _LOOP_RE.fullmatch(op.rpartition("/")[2]):
            total += secs
    return total
