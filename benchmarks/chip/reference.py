"""The plain reference: a float64 sparse lower-triangular solve.

It imports nothing of the program.  It builds its own CSR (paper layout:
off-diagonals by ascending column, diagonal last) from the generator's COO
triples and solves with SciPy's ``spsolve_triangular`` in float64.

`solve_lowp` is the control: the same forward substitution with every
value, right-hand side, product and sum rounded to a lower precision
(bfloat16 for the float32 the configurations state).  `rel_err` is the
number that decides ``correct``.
"""

from __future__ import annotations

import zlib

import ml_dtypes
import numpy as np
import scipy.sparse
import scipy.sparse.linalg

LOWP = {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32}


def csr_arrays(n: int, rows, cols, vals, diag):
    """``(rowptr, colidx, values)`` of the lower-triangular matrix with
    strictly lower COO part ``(rows, cols, vals)`` and diagonal ``diag``."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    if rows.size and np.any(cols >= rows):
        raise ValueError("COO part is not strictly lower triangular")
    key = rows * n + cols
    if np.unique(key).size != key.size:
        raise ValueError("duplicate COO entries")
    r = np.concatenate([rows, np.arange(n, dtype=np.int64)])
    c = np.concatenate([cols, np.arange(n, dtype=np.int64)])
    v = np.concatenate([np.asarray(vals, np.float64),
                        np.asarray(diag, np.float64)])
    order = np.argsort(r * (n + 1) + np.where(r == c, n, c), kind="stable")
    rowptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(r, minlength=n), out=rowptr[1:])
    return rowptr, c[order], v[order]


def fingerprint(rowptr, colidx, values) -> dict:
    """``{"n", "nnz", "crc32"}``; the CRC runs over the int64 rowptr and
    colidx and the float64 values, in that order."""
    crc = 0
    for a, dt in ((rowptr, np.int64), (colidx, np.int64), (values, np.float64)):
        crc = zlib.crc32(np.ascontiguousarray(a, dt).tobytes(), crc)
    return {"n": int(rowptr.size - 1), "nnz": int(rowptr[-1]), "crc32": crc}


class Reference:
    """One matrix, solved in float64 (`solve`) or lower precision."""

    def __init__(self, rowptr, colidx, values):
        self.n = int(rowptr.size - 1)
        self.rowptr, self.colidx, self.values = rowptr, colidx, values
        self.csr = scipy.sparse.csr_matrix((values, colidx, rowptr),
                                           shape=(self.n, self.n))

    def solve(self, b: np.ndarray) -> np.ndarray:
        """float64 solution of ``L x = b`` for ``b`` of shape [n] or [n, k]."""
        b = np.asarray(b, np.float64)
        return scipy.sparse.linalg.spsolve_triangular(self.csr, b, lower=True)

    def solve_lowp(self, b: np.ndarray, dtype: str) -> np.ndarray:
        """Forward substitution with every operation rounded to ``dtype``;
        returns float64 [n, k]."""
        t = LOWP[dtype]
        b = np.asarray(b, np.float64)
        b = (b[:, None] if b.ndim == 1 else b).astype(t)
        vals = self.values.astype(t)
        x = np.zeros(b.shape, t)
        rp, ci = self.rowptr, self.colidx
        for i in range(self.n):
            lo, hi = rp[i], rp[i + 1] - 1
            s = b[i]
            for j in range(lo, hi):
                s = s - vals[j] * x[ci[j]]
            x[i] = s / vals[hi]
        return x.astype(np.float64)


def rel_err(x, ref) -> float:
    """Max over columns of ``||x - ref||_inf / ||ref||_inf``."""
    ref = np.asarray(ref, np.float64)
    ref = ref[:, None] if ref.ndim == 1 else ref
    x = np.asarray(x, np.float64).reshape(ref.shape)
    return float((np.abs(x - ref).max(axis=0)
                  / np.abs(ref).max(axis=0)).max())
