"""HPCG's 27-point stencil (`matrices.stencil27`): the lower triangle that the
forward sweep of its symmetric Gauss-Seidel smoother solves with.

The generator gives HPCG's pattern and values, and the same arrays as the
benchmark's copy; the system (`api.compile` -> `api.solve_batch`) agrees
with the float64 oracle (`csr.serial_solve`) on the jax backend and on the
Pallas resident kernel in interpret mode.  24³ is the smallest cube whose
program spills partial sums, so the spill path stays covered.
"""

import numpy as np
import pytest

from benchmarks.chip import registry
from repro.core import api
from repro.core.csr import serial_solve
from repro.core.matrices import generate, stencil27, stencil27_coo

# float32 solve against the float64 oracle: each row sums at most 13
# products of -1 with |x| bounded by the diagonal 26, so rounding stays near
# float32's 6e-8 (read: 1e-7); bfloat16 reads ~1e-2 here
TOL = 1e-5


def _rel_err(x, ref):
    return np.abs(x - ref).max() / np.abs(ref).max()


@pytest.mark.parametrize("nx", [2, 3, 8])
def test_pattern_and_values_are_hpcg(nx):
    mat = stencil27(nx, f"s{nx}")
    n = nx ** 3
    # a stencil axis holds 3 nx - 2 (row, neighbour) pairs; the lower
    # triangle keeps half of the off-diagonal ones
    full = (3 * nx - 2) ** 3
    assert (mat.n, mat.nnz) == (n, (full - n) // 2 + n)
    diag = mat.rowptr[1:] - 1
    assert (mat.values[diag] == 26.0).all()
    off = np.setdiff1d(np.arange(mat.nnz), diag)
    assert (mat.values[off] == -1.0).all()
    # row (z, y, x) reaches back exactly to its in-grid neighbours
    z, y, x = nx - 1, nx - 1, nx - 1
    last = mat.colidx[mat.rowptr[n - 1]:mat.rowptr[n] - 1]
    want = sorted(((z + a) * nx + (y + b)) * nx + (x + c)
                  for a in (-1, 0) for b in (-1, 0, 1) for c in (-1, 0, 1)
                  if (a, b, c) < (0, 0, 0) and 0 <= y + b < nx
                  and 0 <= x + c < nx)
    assert last.tolist() == want


@pytest.mark.parametrize("nx", [2, 5, 8, 13])
def test_program_generator_equals_benchmark_copy(nx):
    copy = registry.load_code("generators", "stencil27").generate(nx, nx ** 3)
    for a, b in zip(stencil27_coo(nx), copy):
        np.testing.assert_array_equal(a, b)


def test_suite_entries():
    assert generate("hpcg_8").n == 8 ** 3
    assert stencil27_coo(48)[3].size == generate("hpcg_symgs48").n == 110_592


@pytest.mark.parametrize("nx,opts", [
    (8, {"backend": "jax"}),
    (8, {"backend": "pallas", "interpret": True, "placement": "resident"}),
    (24, {"backend": "jax"}),
], ids=["8-jax", "8-pallas-resident", "24-jax"])
def test_solve_matches_float64_oracle(nx, opts):
    mat = stencil27(nx, f"s{nx}")
    prog = api.compile(mat)
    if nx == 24:
        # the smallest cube whose partial sums spill to the register file
        assert prog.stats.spilled_values > 0
    rng = np.random.default_rng(1000 + nx)
    b = rng.standard_normal((mat.n, 2)).astype(np.float32)
    x = api.solve_batch(prog, b, **opts)
    for j in range(b.shape[1]):
        ref = serial_solve(mat, b[:, j].astype(np.float64))
        assert _rel_err(x[:, j], ref) <= TOL
