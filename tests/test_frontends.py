"""DAG-workload frontends: upper / transpose-pair / circuit round-trips.

Each new frontend (core/frontends/) is round-tripped against scipy/numpy
oracles across the executors — the vectorized numpy oracle, the `lax.scan`
JAX executor, and both Pallas placements — plus the batched and sharded
paths, all running the unchanged `Program` format.  Seeded sweeps always
run; hypothesis widens them where it is installed.
"""

import numpy as np
import pytest

from repro.core import api, shard
from repro.core.csr import (
    from_coo,
    serial_solve,
    serial_solve_upper,
    transpose_upper,
)
from repro.core.dag import analyze
from repro.core.frontends.dagcirc import random_circuit
from repro.core.matrices import generate
from repro.core.program import AccelConfig

TOL = dict(rtol=1e-5, atol=1e-5)


def random_lower(n, density, seed, name=None):
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for i in range(1, n):
        m = rng.random(i) < density
        for j in np.nonzero(m)[0]:
            rows.append(i)
            cols.append(int(j))
    vals = rng.uniform(-0.5, 0.5, len(rows))
    diag = rng.uniform(1.0, 2.0, n) * rng.choice([-1.0, 1.0], n)
    return from_coo(n, rows, cols, vals, diag, name=name or f"rnd_{seed}")


# ------------------------------------------------------------------ upper
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_upper_solve_matches_scipy(seed):
    scipy_sparse = pytest.importorskip("scipy.sparse")
    n = 80 + 17 * seed
    u = transpose_upper(random_lower(n, 0.25, seed))
    rng = np.random.default_rng(100 + seed)
    b = rng.standard_normal(n)
    mat = scipy_sparse.csr_matrix(
        (u.values, u.colidx, u.rowptr), shape=(n, n))
    ref = scipy_sparse.linalg.spsolve_triangular(mat, b, lower=False)
    cw = api.compile_upper(u)
    for backend in ("numpy", "jax"):
        np.testing.assert_allclose(cw.solve(b, backend=backend), ref, **TOL)
    np.testing.assert_allclose(serial_solve_upper(u, b), ref, rtol=1e-10)


def test_upper_solve_suite_matrix_all_executors():
    mat = generate("band_cz")
    u = transpose_upper(mat)
    b = np.random.default_rng(7).standard_normal(mat.n)
    ref = serial_solve_upper(u, b)
    cw = api.compile_upper(u)
    np.testing.assert_allclose(cw.solve(b, backend="numpy"), ref, **TOL)
    np.testing.assert_allclose(cw.solve(b, backend="jax"), ref, **TOL)
    np.testing.assert_allclose(
        cw.solve(b, backend="pallas", placement="resident"), ref, **TOL)
    np.testing.assert_allclose(
        cw.solve(b, backend="pallas", placement="blocked"), ref, **TOL)


def test_upper_batched_and_sharded():
    u = transpose_upper(generate("band_cz"))
    n = u.n
    rng = np.random.default_rng(11)
    bmat = rng.standard_normal((n, 8))
    ref = np.stack([serial_solve_upper(u, bmat[:, k]) for k in range(8)],
                   axis=1)
    cw = api.compile_upper(u)
    np.testing.assert_allclose(cw.solve(bmat), ref, **TOL)
    mesh = shard.batch_mesh()
    np.testing.assert_allclose(cw.solve(bmat, mesh=mesh), ref, **TOL)


def test_solve_upper_accepts_raw_matrix():
    u = transpose_upper(random_lower(40, 0.3, 5))
    b = np.random.default_rng(5).standard_normal(40)
    np.testing.assert_allclose(
        api.solve_upper(u, b), serial_solve_upper(u, b), **TOL)


# --------------------------------------------------------- transpose pair
@pytest.mark.parametrize("seed", [3, 4])
def test_compile_pair_ic_sweep(seed):
    """One compiled pair runs the full forward+backward IC application:
    x = Lᵀ \\ (L \\ b) == (L Lᵀ)⁻¹ b."""
    mat = random_lower(70 + 11 * seed, 0.3, seed)
    dense = mat.to_dense()
    rng = np.random.default_rng(200 + seed)
    b = rng.standard_normal(mat.n)
    ref = np.linalg.solve(dense @ dense.T, b)
    pair = api.compile_pair(mat)
    for backend in ("numpy", "jax"):
        got = pair.solve(b, backend=backend)
        np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-5)
    # the backward sweep alone must match the serial upper oracle
    y = serial_solve(mat, b)
    np.testing.assert_allclose(
        pair.backward.solve(y), serial_solve_upper(transpose_upper(mat), y),
        **TOL)


def test_pair_pallas_blocked_placement():
    mat = generate("band_cz")
    pair = api.compile_pair(mat)
    b = np.random.default_rng(13).standard_normal(mat.n)
    dense = mat.to_dense()
    ref = np.linalg.solve(dense @ dense.T, b)
    got = pair.solve(b, backend="pallas", placement="blocked")
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------- circuits
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_circuit_matches_oracle(seed):
    circ = random_circuit(120 + 40 * seed, max_fan_in=5, seed=seed,
                          locality=60 if seed % 2 else None)
    cw = api.compile_circuit(circ)
    rng = np.random.default_rng(300 + seed)
    u = rng.standard_normal(circ.n)
    ref = circ.eval(u)
    for backend in ("numpy", "jax"):
        np.testing.assert_allclose(cw.solve(u, backend=backend), ref, **TOL)


def test_circuit_pallas_and_batched():
    circ = random_circuit(256, max_fan_in=4, seed=9, locality=48)
    cw = api.compile_circuit(circ)
    rng = np.random.default_rng(42)
    umat = rng.standard_normal((circ.n, 4))
    ref = circ.eval(umat)
    np.testing.assert_allclose(cw.solve(umat), ref, **TOL)
    np.testing.assert_allclose(
        cw.solve(umat, backend="pallas", placement="resident"), ref, **TOL)


def test_circuit_pallas_blocked_placement(small_row_blocks):
    """Strongly-local circuits admit the row-blocked window placement."""
    circ = random_circuit(1024, max_fan_in=4, seed=21, locality=48)
    cw = api.compile_circuit(circ)
    from repro.kernels.sptrsv import ops

    plan = ops.plan_window(cw.program)
    assert plan.feasible and plan.num_blocks > 1
    u = np.random.default_rng(1).standard_normal((circ.n, 4))
    got = cw.solve(u, backend="pallas", placement="blocked")
    np.testing.assert_allclose(got, circ.eval(u), **TOL)


def test_circuit_stats_and_analysis():
    """Generic DAG workloads get the paper's Table III treatment too."""
    circ = random_circuit(300, seed=4)
    info = analyze(circ)
    assert info.n == 300 and info.nnz == circ.n_edges + circ.n
    prog = api.compile_circuit(circ, AccelConfig()).program
    assert prog.stats.exec_edges == circ.n_edges
    assert prog.stats.exec_finals == circ.n
    rep = api.report(prog)
    assert rep["emitted_cycles"] == prog.cycles          # satellite: report
    assert rep["planes"] == prog.planes                  # exposes PR-4
    assert rep["instr_bytes"] == prog.instr_bytes()      # encoding fields


# -------------------------------------------------- hypothesis wide sweeps
def test_upper_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(2, 70), st.floats(0.0, 0.5),
           st.integers(0, 2**31 - 1))
    def run(n, density, seed):
        u = transpose_upper(random_lower(n, density, seed))
        b = np.random.default_rng(seed ^ 0xABC).standard_normal(n)
        cw = api.compile_upper(u)
        ref = serial_solve_upper(u, b)
        np.testing.assert_allclose(cw.solve(b, backend="numpy"), ref, **TOL)

    run()


def test_circuit_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(2, 120), st.integers(1, 8),
           st.floats(0.05, 0.9), st.integers(0, 2**31 - 1))
    def run(n, fan_in, leaf_frac, seed):
        circ = random_circuit(n, max_fan_in=fan_in, leaf_frac=leaf_frac,
                              seed=seed)
        u = np.random.default_rng(seed ^ 0x5A5).standard_normal(n)
        cw = api.compile_circuit(circ)
        np.testing.assert_allclose(cw.solve(u, backend="numpy"),
                                   circ.eval(u), **TOL)

    run()


# ------------------------------------------------------- benchmark wiring
def test_dag_workloads_smoke():
    """Tier-1 guard on the DAG-workload benchmark (satellite: CI wiring)."""
    from benchmarks.dag_workloads import run

    rows = run(smoke=True)
    assert rows, "smoke set is empty"
    workloads = {r["workload"] for r in rows}
    assert {"lower", "upper", "transpose_pair", "circuit"} <= workloads
    for r in rows:
        assert r["max_err"] <= 1e-5, r
        assert r["cycles"] >= 1 and r["emitted_cycles"] <= r["cycles"], r
