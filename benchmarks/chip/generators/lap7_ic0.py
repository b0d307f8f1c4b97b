"""The IC(0) factor of the 3D 7-point Laplacian: a copy of the program's
``core.matrices.laplace7_ic0_coo``.

Kept with the benchmark so that the yardstick does not move when the
program's own generators change.  The matrix is hypre's ``ij -laplacian``
problem on one rank's nx × nx × nx local grid: rows in natural order (x
fastest), diagonal 6, each in-grid neighbour -1.  ICCG applies the
incomplete Cholesky preconditioner with no fill (Meijerink and van der
Vorst, Math. Comp. 31 (1977) 148-162) once per iteration; its forward
solve L̃ y = r is the system here.  The 7-point graph has no triangles, so
the factor has a closed form: ``d_i = 6 - Σ_j 1/d_j`` over the lower
neighbours j of i, ``L̃_ii = √d_i``, ``L̃_ij = -1/√d_j``.
"""

from __future__ import annotations

import numpy as np


def generate(nx: int, n: int):
    """The strictly lower COO part sorted by (row, col), and the diagonal,
    of the first ``n`` rows of L̃ (``nx ** 3`` for the whole grid).

    A leading block of a lower-triangular matrix is the system its first
    ``n`` unknowns solve, so a smaller ``n`` gives a test-sized problem with
    the same rows.  Returns ``(rows, cols, vals, diag)``: int64, int64,
    float64, float64.
    """
    if not 0 < n <= nx ** 3:
        raise ValueError(f"n={n} rows: a {nx}^3 grid has 1 to {nx ** 3}")
    g = np.arange(nx ** 3, dtype=np.int64)
    ix, iy, iz = g % nx, (g // nx) % nx, g // (nx * nx)
    offs = ((ix > 0, 1), (iy > 0, nx), (iz > 0, nx * nx))
    # d, one wavefront ix + iy + iz at a time: a row needs only the one
    # before it
    inv_d = np.zeros(g.size)
    front = np.argsort(ix + iy + iz, kind="stable")
    ends = np.cumsum(np.bincount(ix + iy + iz))
    for lo, hi in zip(ends - np.diff(ends, prepend=0), ends):
        r = front[lo:hi]
        d = np.full(r.size, 6.0)
        for has, off in offs:
            d -= np.where(has[r], inv_d[r - off * has[r]], 0.0)
        inv_d[r] = 1.0 / d
    rows, cols = [], []
    for has, off in offs:
        keep = has[:n]
        rows.append(g[:n][keep])
        cols.append(g[:n][keep] - off)
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    return rows, cols, -np.sqrt(inv_d[cols]), 1.0 / np.sqrt(inv_d[:n])
