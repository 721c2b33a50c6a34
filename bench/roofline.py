"""Work a CP-ALS sweep needs, counted from its shapes, whatever implements it.

The MTTKRP of one mode update (the "EC", elementwise computation) must at
least read each nonzero once (value 4 B, ``nmodes`` int32 coordinates),
gather one factor row of ``rank`` float32 for each of the ``nin = nmodes -
1`` input modes, and write the ``rows × rank`` float32 output; it multiplies
``nin`` rows into the value and adds the product into its row:

    bytes = nnz · (4 + 4·nmodes + 4·rank·nin) + rows · rank · 4
    flops = nnz · rank · (nin + 1)

``rank`` is the model's rank, not a lane-padded width. The solve of one
mode update (``M V⁻¹``, the column norms, the new Gram) reads ``M``,
writes ``F`` and reads it again for the Gram, and does two ``rows × R × R``
products:

    bytes = 3 · rows · rank · 4,   flops = 4 · rows · rank²

The least time of a piece of work is the larger of ``bytes`` over the HBM
bandwidth and ``flops`` over the peak FLOP rate; ``bound`` says which.
"""
from __future__ import annotations

__all__ = ["ec_cost", "solve_cost", "least_time", "sweep_least_time"]


def ec_cost(nnz: int, nmodes: int, rank: int, rows: int) -> tuple[int, int]:
    """(bytes, flops) of one mode's MTTKRP."""
    nin = nmodes - 1
    nbytes = nnz * (4 + 4 * nmodes + 4 * rank * nin) + rows * rank * 4
    flops = nnz * rank * (nin + 1)
    return nbytes, flops


def solve_cost(rows: int, rank: int) -> tuple[int, int]:
    """(bytes, flops) of one mode's solve, normalization and Gram."""
    return 3 * rows * rank * 4, 4 * rows * rank * rank


def least_time(nbytes: float, flops: float, peaks: dict) -> tuple[float, str]:
    """(seconds, "bytes" | "flops") at the chip's peaks."""
    tb = nbytes / peaks["hbm_bytes_per_s"]
    tf = flops / peaks["flops_per_s"]
    return (tb, "bytes") if tb >= tf else (tf, "flops")


def sweep_least_time(nnz: int, shape, rank: int, peaks: dict, *,
                     solve: bool) -> float:
    """Least seconds of one sweep's MTTKRPs (and, with ``solve``, of its
    solves too), summed over the modes."""
    total = 0.0
    for rows in shape:
        total += least_time(*ec_cost(nnz, len(shape), rank, rows), peaks)[0]
        if solve:
            total += least_time(*solve_cost(rows, rank), peaks)[0]
    return total
