"""Bytes and operations a sparse lower-triangular solve needs.

These are the algorithm's, fixed by the matrix and the number of
right-hand sides, and do not depend on what the compiler emits: a kernel
that moves more than this, or computes more, is charged for it in its
roofline share.

* bytes: each non-zero's value and column index (4 + 4 B in float32 and
  int32), the row pointers (4 B each, n + 1 of them), each right-hand side
  read and each solution written once (4 + 4 B per row and column);
* operations: per column, a multiply and an add per off-diagonal non-zero
  and one divide per row, ``2 * nnz - n`` (the paper's binary nodes).
"""

from __future__ import annotations


def solve_bytes(n: int, nnz: int, columns: int) -> int:
    return 8 * nnz + 4 * (n + 1) + 8 * n * columns


def solve_flops(n: int, nnz: int, columns: int) -> int:
    return (2 * nnz - n) * columns


def least_seconds(n: int, nnz: int, columns: int, peak: dict) -> tuple:
    """(seconds, bound): the larger of bytes over peak bandwidth and
    operations over peak rate, and which of the two it is."""
    t_mem = solve_bytes(n, nnz, columns) / peak["hbm_bytes_per_s"]
    t_ops = solve_flops(n, nnz, columns) / peak["flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
