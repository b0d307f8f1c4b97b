"""HPCG's 27-point stencil generator: a copy of the program's
``core.matrices.stencil27_coo``.

Kept with the benchmark so that the yardstick does not move when the
program's own generators change.  HPCG's ``GenerateProblem`` on one rank's
nx × nx × nx local grid: rows in natural order (x fastest), diagonal 26,
each in-grid neighbour -1.  The lower triangle with the diagonal is what
the forward sweep of ``ComputeSYMGS`` solves with.
"""

from __future__ import annotations

import numpy as np


def generate(nx: int, n: int):
    """The strictly lower COO part sorted by (row, col), and the diagonal,
    of the first ``n`` rows of the sweep (``nx ** 3`` for the whole grid).

    A leading block of a lower-triangular matrix is the system its first
    ``n`` unknowns solve, so a smaller ``n`` gives a test-sized problem with
    the same rows.  Returns ``(rows, cols, vals, diag)``: int64, int64,
    float64, float64.
    """
    if not 0 < n <= nx ** 3:
        raise ValueError(f"n={n} rows: a {nx}^3 grid has 1 to {nx ** 3}")
    g = np.arange(n, dtype=np.int64)
    ix, iy, iz = g % nx, (g // nx) % nx, g // (nx * nx)
    rows, cols = [], []
    for sz in (-1, 0, 1):
        for sy in (-1, 0, 1):
            for sx in (-1, 0, 1):
                off = (sz * nx + sy) * nx + sx
                if off >= 0:  # the diagonal and the upper triangle
                    continue
                ok = ((0 <= iz + sz) & (iz + sz < nx) & (0 <= iy + sy)
                      & (iy + sy < nx) & (0 <= ix + sx) & (ix + sx < nx))
                rows.append(g[ok])
                cols.append(g[ok] + off)
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    order = np.lexsort((cols, rows))
    return (rows[order], cols[order], np.full(rows.size, -1.0),
            np.full(n, 26.0))
