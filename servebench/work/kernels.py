"""Operations and bytes of one call of each Pallas kernel on the serving
path, from the shapes it is given (see ``repro/kernels`` for the calls).

A kernel's least time is the larger of its operations over the peak
FLOP/s and its bytes over the peak bytes/s.
"""
from __future__ import annotations

from typing import Dict, Tuple


def least_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def paged_gather(rows: int, table_width: int, page: int, width: int,
                 itemsize: int) -> Tuple[float, float]:
    """Copy each row's ``table_width`` pages of ``page`` x ``width`` to a
    contiguous history: every page read once, the history written once."""
    return 0.0, 2.0 * rows * table_width * page * width * itemsize


def srf_decode(rows: int, heads: int, m: int, dv: int,
               itemsize: int = 4) -> Tuple[float, float]:
    """State update s += phi_k v^T, z += phi_k and readout phi_q s / phi_q z
    for ``rows`` x ``heads``; every operand float32 as the engine passes it."""
    bh = rows * heads
    flops = bh * (2.0 * m * dv + m + 2.0 * m * dv + 2.0 * m)
    read = bh * (m * dv + m + 2 * m + dv)        # s, z, phi_q, phi_k, v
    write = bh * (m * dv + m + dv)               # s', z', out
    return flops, (read + write) * itemsize


def spinner(groups: int, rows: int, n: int, m: int,
            itemsize: int) -> Tuple[float, float]:
    """Fused HD + structured projection + epilogue, per group: one n x n
    Hadamard product and one n x m projection per row, as the kernel runs
    them on the matrix unit; reads x and the group's generators, writes
    the features."""
    flops = groups * rows * 2.0 * n * (n + m)
    nbytes = groups * (rows * (n + m) + (-(-m // n)) * n + 2 * n) * itemsize
    return flops, nbytes
