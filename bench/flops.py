"""Operations and bytes of one kernel call, from its shapes, and the least
time the chip could take for them.

Each function returns ``{"flops": ..., "bytes": ...}`` for one call: the
work the algorithm needs, counted once, with every operand read once and
the result written once."""
from __future__ import annotations

F32 = 4


def fedavg_reduce(clients: int, n: int, centered: bool = True) -> dict:
    """(C, n) client rows, minus the (n,) center where ``centered``, times
    the (C,) weights, summed over C into an (n,) fp32 output."""
    reads = clients * n + clients + (n if centered else 0)
    return {"flops": (3 if centered else 2) * clients * n,
            "bytes": F32 * (reads + n)}


def topk_scatter_reduce(clients: int, k: int, n: int) -> dict:
    """(C, k) int32 indices and fp32 values, times the (C,) weights,
    scatter-added into an (n,) fp32 accumulator that starts at zero."""
    return {"flops": 2 * clients * k,
            "bytes": F32 * (2 * clients * k + clients + n)}


def least_seconds(work: dict, peaks: dict) -> float:
    """The roofline: the larger of the compute and the HBM bound."""
    return max(work["flops"] / peaks["bf16_flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])


def bound(work: dict, peaks: dict) -> str:
    """Which of the two bounds ``least_seconds`` took."""
    compute = work["flops"] / peaks["bf16_flops_per_s"]
    return "compute" if compute >= work["bytes"] / peaks["hbm_bytes_per_s"] else "hbm"
