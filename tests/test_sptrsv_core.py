"""System behaviour tests for the SpTRSV core (compiler + executors)."""

import dataclasses

import numpy as np
import pytest

from repro.core import api
from repro.core.csr import TriCSR, from_coo, random_rhs, serial_solve
from repro.core.dag import analyze, compute_levels
from repro.core.matrices import SUITE, generate
from repro.core.program import AccelConfig
from repro.core.compiler import compile_program

SMALL = ["chain_1k", "band_cz", "ckt_rajat04", "chem_bp", "wide_c36", "hub_small"]


def test_csr_validation_and_serial_solve():
    mat = from_coo(4, [1, 2, 3, 3], [0, 1, 0, 2], [-1, -1, -1, -1],
                   np.ones(4), "tiny")
    b = np.array([1.0, 2.0, 3.0, 4.0])
    x = serial_solve(mat, b)
    # forward substitution by hand
    assert np.allclose(x, [1.0, 3.0, 6.0, 11.0])


def _from_coo_loop(n, rows, cols, vals, diag):
    """The per-entry loop `from_coo` placed entries with before it was
    vectorised: duplicates keep the last, each row's off-diagonals by
    ascending column, the diagonal last."""
    last = {}
    for r, c, v in zip(rows, cols, vals):
        last[(r, c)] = v
    rowptr, colidx, values = [0], [], []
    for i in range(n):
        for c in sorted(c for r, c in last if r == i):
            colidx.append(c)
            values.append(last[(i, c)])
        colidx.append(i)
        values.append(diag[i])
        rowptr.append(len(colidx))
    return rowptr, colidx, values


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_from_coo_matches_entry_loop(seed):
    rng = np.random.default_rng(seed)
    n = 40
    rows = rng.integers(1, n, 300)  # with duplicates, in no order
    cols = (rng.random(300) * rows).astype(np.int64)
    vals = rng.standard_normal(300)
    diag = rng.uniform(1.0, 2.0, n)
    mat = from_coo(n, rows.tolist(), cols, vals, diag)
    want = _from_coo_loop(n, rows.tolist(), cols.tolist(), vals.tolist(),
                          diag)
    for got, ref in zip((mat.rowptr, mat.colidx, mat.values), want):
        np.testing.assert_array_equal(got, np.asarray(ref))


def test_levels_match_longest_path():
    mat = generate("chain_1k")
    lv = compute_levels(mat)
    assert lv[0] == 0
    assert lv[-1] == mat.n - 1  # bidiagonal chain: level == row index


def test_dag_stats_table3_fields():
    info = analyze(generate("band_cz"))
    row = info.row()
    assert row["binary_nodes"] == 2 * row["nnz"] - row["n"]
    assert 0 <= row["cdu_nodes_pct"] <= 100


@pytest.mark.parametrize("name", SMALL)
def test_medium_program_correct(name):
    mat = generate(name)
    prog = api.compile(mat)
    b = random_rhs(mat, 7)
    ref = serial_solve(mat, b)
    got = api.solve_numpy(prog, b)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("name", ["band_cz", "ckt_rajat04", "wide_c36"])
def test_jax_executor_matches_numpy(name):
    mat = generate(name)
    prog = api.compile(mat)
    b = random_rhs(mat, 8)
    np.testing.assert_allclose(
        api.solve(prog, b), api.solve_numpy(prog, b), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("name", ["band_cz", "chem_bp", "hub_small"])
def test_coarse_program_correct(name):
    mat = generate(name)
    prog = api.baseline_coarse(mat)
    b = random_rhs(mat, 9)
    np.testing.assert_allclose(
        api.solve_numpy(prog, b), serial_solve(mat, b), rtol=2e-4, atol=1e-4
    )


def test_serial_chain_cycle_count():
    """Bidiagonal chain is inherently serial: exactly 2n-1 cycles
    (edge+finalize per node, pipelined by one)."""
    mat = generate("chain_1k")
    prog = api.compile(mat)
    assert prog.stats.cycles == 2 * mat.n - 1


def test_cycles_lower_bound():
    for name in SMALL:
        mat = generate(name)
        prog = api.compile(mat)
        assert prog.stats.cycles >= mat.nnz / prog.config.num_cus


def test_every_op_scheduled_exactly_once():
    mat = generate("ckt_rajat04")
    prog = api.compile(mat)
    assert prog.stats.exec_edges == mat.nnz - mat.n
    assert prog.stats.exec_finals == mat.n
    # each x index finalized exactly once
    finals = prog.out_idx[prog.opcode == 2]
    assert len(np.unique(finals)) == mat.n


def test_medium_beats_coarse_on_cdu_heavy():
    """The paper's central claim (Fig. 9a)."""
    for name in ["band_dw2048", "ckt_add20", "grid_activsg"]:
        mat = generate(name)
        med = api.compile(mat).stats.cycles
        coa = api.baseline_coarse(mat).stats.cycles
        assert med < coa, (name, med, coa)


def test_psum_caching_reduces_cycles():
    """Fig. 9b/c: enabling the psum cache reduces total cycles."""
    mat = generate("ckt_rajat04")
    with_c = compile_program(mat, AccelConfig(psum_cache=True)).stats
    no_c = compile_program(mat, AccelConfig(psum_cache=False)).stats
    assert with_c.cycles <= no_c.cycles
    # still correct without the mechanism
    b = random_rhs(mat, 10)
    prog = compile_program(mat, AccelConfig(psum_cache=False))
    np.testing.assert_allclose(
        api.solve_numpy(prog, b), serial_solve(mat, b), rtol=2e-4, atol=1e-4
    )


def test_icr_improves_reuse():
    """Fig. 9f: ICR increases broadcast reuse events."""
    mat = generate("band_dw2048")
    icr = compile_program(mat, AccelConfig(icr=True)).stats
    no = compile_program(mat, AccelConfig(icr=False)).stats
    assert icr.reuse_events >= no.reuse_events
    assert icr.constraints <= no.constraints


def test_icr_preserves_correctness():
    mat = generate("band_cz")
    b = random_rhs(mat, 11)
    for icr in (True, False):
        prog = compile_program(mat, AccelConfig(icr=icr))
        np.testing.assert_allclose(
            api.solve_numpy(prog, b), serial_solve(mat, b), rtol=2e-4, atol=1e-4
        )


def test_roundrobin_alloc_correct():
    mat = generate("chem_bp")
    prog = compile_program(mat, AccelConfig(alloc="roundrobin"))
    b = random_rhs(mat, 12)
    np.testing.assert_allclose(
        api.solve_numpy(prog, b), serial_solve(mat, b), rtol=2e-4, atol=1e-4
    )


def test_dm_escape_program_still_correct():
    """Programs that needed emergency psum overflow must stay exact."""
    mat = generate("ckt_rajat04")
    prog = compile_program(mat, AccelConfig(psum_words=2))
    assert prog.stats.dm_escapes >= 0
    b = random_rhs(mat, 13)
    np.testing.assert_allclose(
        api.solve_numpy(prog, b), serial_solve(mat, b), rtol=2e-4, atol=1e-4
    )


def test_nop_breakdown_sums_to_one():
    mat = generate("chem_bp")
    st = api.compile(mat).stats
    total = sum(st.nop_breakdown().values())
    assert abs(total - 1.0) < 1e-9


def test_throughput_below_peak():
    for name in SMALL:
        st = api.compile(generate(name)).stats
        cfg = AccelConfig()
        assert st.throughput_gops(cfg) <= st.peak_throughput_gops(cfg) + 1e-9


def test_fine_baseline_runs():
    st = api.baseline_fine(generate("band_cz"))
    assert st.blocks >= st.n
    assert st.effective_cycles > 0


def test_suite_generators_all_valid():
    for name in SUITE:
        mat = generate(name)
        mat.validate()
