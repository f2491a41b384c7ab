"""Operation and byte counts of one stream step of exact sparse RTRL.

The benchmark's own yardstick, kept apart from the program so that no change
to the program can change how its work is counted.  The influence counts are
copied from the program's cost model (`core/costs.py`,
`ragged_influence_update_flops` and `influence_update_bytes`); the forward
and gradient-readout counts are added here.

Notation: B examples per stream, n hidden units, n_in inputs, n_out outputs,
Pc live influence columns (the parameters that the fixed masks keep), K_b
the live rows of example b's influence (units with a nonzero
pseudo-derivative), K'_b the same one step earlier.  A multiply-add counts
as 2 operations.
"""
from __future__ import annotations

import numpy as np


def ragged_influence_update_flops(Kbs, Kbs_prev, Pc: int) -> float:
    """Operations of one ragged influence update: sum_b 2 K_b K'_b Pc.

    Only live rows of the previous influence feed live rows of the new one,
    so this is the exact update's least work at the measured activity."""
    Kbs = np.asarray(Kbs, float)
    Kbs_prev = np.asarray(Kbs_prev, float)
    return float(2.0 * Pc * np.sum(Kbs * Kbs_prev))


def influence_update_bytes(B: int, K: int, K_prev: int, Pc: int, n: int,
                           dtype_bytes: int = 4) -> int:
    """Least HBM traffic of one influence update: read the carry
    [B, K_prev, Pc] and write [B, K, Pc] at the carry dtype, the f32 J-hat
    [B, n, n], the f32 immediate-influence rows [B, K, Pc], and the int32
    index and count side arrays."""
    carry = (B * K_prev * Pc + B * K * Pc) * dtype_bytes
    jhat = B * n * n * 4
    mbar = B * K * Pc * 4
    side = 2 * B * K * 4 + B * K * 4 + 2 * B * 4
    return carry + jhat + mbar + side


def forward_flops(B: int, nnz_weights: int, n: int, n_out: int) -> float:
    """Operations of one masked EGRU forward step and its readout: every
    kept input and recurrent weight is one multiply-add per example, the
    readout n x n_out more."""
    return 2.0 * B * (nnz_weights + n * n_out)


def grad_readout_flops(Kbs, Pc: int) -> float:
    """Operations of one step's gradient readout from the influence rows:
    sum_b 2 K_b Pc (dL/da_b contracted with its live influence rows)."""
    return float(2.0 * Pc * np.sum(np.asarray(Kbs, float)))


def step_flops(B: int, n: int, n_out: int, nnz_weights: int, Pc: int,
               live_rows: float) -> float:
    """Required operations of one stream step at `live_rows` live influence
    rows per example (now and one step earlier alike): forward, influence
    update and gradient readout."""
    kb = np.full(B, float(live_rows))
    return (forward_flops(B, nnz_weights, n, n_out)
            + ragged_influence_update_flops(kb, kb, Pc)
            + grad_readout_flops(kb, Pc))


def least_time_s(flops: float, nbytes: float, peak_flops: float,
                 peak_bytes_per_s: float) -> tuple[float, str]:
    """(least seconds, binding bound): the larger of operations over peak
    operations per second and bytes over peak bandwidth."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
