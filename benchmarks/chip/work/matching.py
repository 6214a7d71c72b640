"""The work the matching LP's dual oracle needs, counted from the
configuration's sizes, not from how the program lays it out.

Per edge, one evaluation of the oracle must read the destination index
(4 B), the m constraint coefficients and the cost at the storage width, and
do a few operations: the m products of A^T lam, the scaled cost, the
projection's compare, subtract and clamp, and the m products of A x.  Once
per evaluation it reads the m*J duals and writes the m*J entries of A x.
Padded slots, intermediates the implementation keeps in memory (such as a
stored A^T lam slab) and the O(J) compares of a one-hot histogram are not
work the LP needs, and are not counted.  One power-iteration step reads the
index and the coefficients twice over (A^T u, then A of it).

`least_seconds` is the time the chip needs at least for that work: the
larger of bytes at peak memory bandwidth and operations at peak rate.
"""
from __future__ import annotations

__all__ = ["oracle_eval", "power_step", "least_seconds"]


def oracle_eval(nnz: int, m: int, J: int, slab_bytes: int = 4) -> tuple:
    """(bytes, operations) of one dual-oracle evaluation."""
    nbytes = nnz * (4 + (m + 1) * slab_bytes) + 2 * m * J * 4
    ops = nnz * (4 * m + 6)
    return nbytes, ops


def power_step(nnz: int, m: int, J: int, slab_bytes: int = 4) -> tuple:
    """(bytes, operations) of one power-iteration step, A (A^T u)."""
    nbytes = 2 * nnz * (4 + m * slab_bytes) + 2 * m * J * 4
    ops = nnz * 4 * m
    return nbytes, ops


def least_seconds(work: tuple, peaks: dict) -> float:
    nbytes, ops = work
    return max(nbytes / peaks["hbm_bytes_per_s"], ops / peaks["flops_per_s"])
