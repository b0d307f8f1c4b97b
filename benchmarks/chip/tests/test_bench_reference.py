"""`reference.py` against a dense float64 solve, and its control."""

import numpy as np
import pytest

from benchmarks.chip import reference


def _small(n=40, seed=0):
    rng = np.random.default_rng(seed)
    rows, cols = np.tril_indices(n, -1)
    keep = rng.random(rows.size) < 0.3
    rows, cols = rows[keep], cols[keep]
    vals = rng.uniform(-0.5, 0.5, rows.size)
    diag = rng.uniform(1.0, 2.0, n) * rng.choice([-1.0, 1.0], n)
    dense = np.diag(diag)
    dense[rows, cols] = vals
    return n, (rows, cols, vals, diag), dense


def test_csr_layout_diagonal_last():
    n, coo, dense = _small()
    rowptr, colidx, values = reference.csr_arrays(n, *coo)
    assert rowptr[-1] == np.count_nonzero(dense)
    np.testing.assert_array_equal(colidx[rowptr[1:] - 1], np.arange(n))
    for i in range(n):
        lo, hi = rowptr[i], rowptr[i + 1]
        np.testing.assert_array_equal(colidx[lo:hi - 1],
                                      np.flatnonzero(dense[i, :i]))
        np.testing.assert_array_equal(values[lo:hi], dense[i, colidx[lo:hi]])


def test_matches_dense_solve():
    n, coo, dense = _small()
    ref = reference.Reference(*reference.csr_arrays(n, *coo))
    b = np.random.default_rng(1).standard_normal((n, 3))
    np.testing.assert_allclose(ref.solve(b), np.linalg.solve(dense, b),
                               rtol=1e-12, atol=1e-12)
    assert ref.solve(b[:, 0]).shape == (n,)


def test_lowp_forward_substitution_is_rounded():
    n, coo, dense = _small()
    ref = reference.Reference(*reference.csr_arrays(n, *coo))
    b = np.random.default_rng(2).standard_normal((n, 4))
    exact = ref.solve(b)
    assert reference.rel_err(ref.solve_lowp(b, "float32"), exact) < 1e-6
    assert reference.rel_err(ref.solve_lowp(b, "bfloat16"), exact) > 1e-3


def test_rel_err_is_worst_column():
    ref = np.array([[1.0, 10.0], [2.0, 20.0]])
    x = ref.copy()
    x[0, 1] += 2.0
    assert reference.rel_err(x, ref) == pytest.approx(0.1)


def test_duplicates_and_upper_entries_refused():
    with pytest.raises(ValueError):
        reference.csr_arrays(3, [1, 1], [0, 0], [1.0, 2.0], np.ones(3))
    with pytest.raises(ValueError):
        reference.csr_arrays(3, [0], [1], [1.0], np.ones(3))
