"""Hardened solve path: validator, taxonomy, ladder, fault injection.

Covers the four layers of DESIGN.md §7: `verify_program` (clean on every
frontend's output, and each structural invariant violated in isolation is
caught and named), the exception taxonomy (every leaf keeps its historical
builtin), the unified backend-dispatch rejections (one test per rejected
combination), CSR validation as structured `MatrixValidationError`s that
survive ``python -O``, the `RobustSolver` degradation ladder (oracle-equal
results after every forced degradation stage, deterministic deadlines on
an injected clock, bounded retries, incident trails), and the end-to-end
fault-injection smoke tier of `benchmarks/robust_overhead.py`.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import api
from repro.core.csr import TriCSR, from_coo, random_rhs, serial_solve, transpose_upper
from repro.core.errors import (
    BackendExecutionError,
    BackendOptionsError,
    MatrixValidationError,
    NumericalHealthError,
    PlacementInfeasibleError,
    ProgramCorruptionError,
    RobustnessError,
    UnknownBackendError,
)
from repro.core.frontends.dagcirc import random_circuit
from repro.core.matrices import generate
from repro.core.program import (
    PS_LOAD,
    PS_STORE_RESET,
    PS_SWAP,
    decode_instructions,
    pack_instructions,
)
from repro.core.robust import (
    FAULT_CLASSES,
    LADDER,
    FaultInjector,
    RobustSolver,
    relative_residual,
    run_fault_injection,
    verify_program,
)
from repro.kernels.sptrsv import ops

TOL = dict(rtol=1e-5, atol=1e-5)  # jax rungs compute in float32


@pytest.fixture(scope="module")
def band():
    mat = generate("band_cz")
    return mat, api.compile(mat)


@pytest.fixture(scope="module")
def ckt():
    mat = generate("ckt_rajat04")  # psum-heavy, blocked-infeasible
    return mat, api.compile(mat)


def _repack(prog, op, src, ctl, slot):
    return dataclasses.replace(
        prog, instr=pack_instructions(op, src, ctl, slot, planes=prog.planes))


# ===================================================== verify_program: clean
def test_verify_clean_on_lower(band, ckt):
    verify_program(band[1])
    verify_program(ckt[1])


def test_verify_clean_on_upper_and_circuit(band):
    verify_program(api.compile_upper(transpose_upper(band[0])).program)
    circ = random_circuit(160, max_fan_in=5, seed=4, locality=48)
    verify_program(api.compile_circuit(circ).program)


# ============================================ verify_program: each invariant
def test_verify_rejects_nonfinite_stream(band):
    bad = FaultInjector(0).corrupt_stream(band[1], k=2, mode="nan")
    with pytest.raises(ProgramCorruptionError, match="non-finite"):
        verify_program(bad)


def test_verify_rejects_val_idx_out_of_bounds(band):
    bad = dataclasses.replace(band[1], val_idx=band[1].val_idx.copy())
    bad.val_idx[3, 0] = bad.stream.size + 7
    with pytest.raises(ProgramCorruptionError, match="bounds"):
        verify_program(bad)


def test_verify_rejects_nonzero_nop_lane(band):
    prog = band[1]
    op, src, ctl, slot = decode_instructions(prog.instr, prog.planes)
    t, p = np.argwhere(op == 0)[0]  # a NOP lane (pad rows guarantee some)
    src = src.copy()
    src[t, p] = 1  # bits flipped into a field the executor ignores on NOP
    with pytest.raises(ProgramCorruptionError, match="NOP lane"):
        verify_program(_repack(prog, op, src, ctl, slot))


def test_verify_rejects_src_beyond_n(band):
    prog = band[1]
    op, src, ctl, slot = decode_instructions(prog.instr, prog.planes)
    t, p = np.argwhere(op != 0)[0]
    src = src.copy()
    src[t, p] = prog.n + 5
    with pytest.raises(ProgramCorruptionError, match="reads row"):
        verify_program(_repack(prog, op, src, ctl, slot))


def test_verify_rejects_duplicate_final(band):
    prog = band[1]
    op, src, ctl, slot = decode_instructions(prog.instr, prog.planes)
    finals = np.argwhere(op == 2)
    (t0, p0), (t1, p1) = finals[0], finals[1]
    src = src.copy()
    src[t1, p1] = src[t0, p0]  # row finalized twice, another never
    with pytest.raises(ProgramCorruptionError, match="finalized"):
        verify_program(_repack(prog, op, src, ctl, slot))


def test_verify_rejects_dependency_order_violation(band):
    """Reversing the cycle axis breaks topology."""
    prog = band[1]
    bad = dataclasses.replace(
        prog,
        instr=prog.instr[::-1].copy(),
        val_idx=prog.val_idx[::-1].copy(),
    )
    with pytest.raises(ProgramCorruptionError, match="dependency order"):
        verify_program(bad)


def test_verify_rejects_zero_final_reciprocal(band):
    prog = band[1]
    op, _, _, _ = decode_instructions(prog.instr, prog.planes)
    t, p = np.argwhere(op == 2)[0]
    bad = dataclasses.replace(prog, stream=prog.stream.copy())
    bad.stream[prog.val_idx[t, p]] = 0.0
    with pytest.raises(ProgramCorruptionError, match="zero diagonal"):
        verify_program(bad)


def test_verify_rejects_load_before_store(ckt):
    prog = ckt[1]
    from repro.core.executor import _psum_slots

    op, src, ctl, slot = decode_instructions(prog.instr, prog.planes)
    nslots = _psum_slots(prog)
    store = (ctl == PS_STORE_RESET) | (ctl == PS_SWAP)
    # inject a LOAD of a slot no earlier instruction on that CU has stored
    for t, p in np.argwhere((op != 0) & (ctl == 0)):
        stored = set(slot[:t, p][store[:t, p]].tolist())
        s = next((s for s in range(nslots) if s not in stored), None)
        if s is not None:
            break
    assert s is not None, "no injectable lane found"
    ctl, slot = ctl.copy(), slot.copy()
    ctl[t, p], slot[t, p] = PS_LOAD, s
    with pytest.raises(ProgramCorruptionError, match="psum lifetime"):
        verify_program(_repack(prog, op, src, ctl, slot))


def test_verify_rejects_slot_beyond_register_file(ckt):
    prog = ckt[1]
    bad = FaultInjector(1).corrupt_slots(prog, k=4)
    with pytest.raises(ProgramCorruptionError):
        verify_program(bad)  # slot range or lifetime, depending on rewrite


# =========================================================== error taxonomy
@pytest.mark.parametrize("leaf,builtin", [
    (ProgramCorruptionError, ValueError),
    (MatrixValidationError, ValueError),
    (NumericalHealthError, ValueError),
    (BackendExecutionError, RuntimeError),
    (UnknownBackendError, ValueError),
    (BackendOptionsError, TypeError),
    (PlacementInfeasibleError, ValueError),
])
def test_taxonomy_keeps_historical_builtin(leaf, builtin):
    err = leaf("boom", detail={"k": 1})
    assert isinstance(err, RobustnessError) and isinstance(err, builtin)
    assert err.detail == {"k": 1}


def test_taxonomy_hierarchy():
    assert issubclass(UnknownBackendError, BackendExecutionError)
    assert issubclass(BackendOptionsError, BackendExecutionError)
    assert issubclass(PlacementInfeasibleError, BackendExecutionError)


# ============================================== backend dispatch rejections
def test_unknown_backend_rejected(band):
    b = random_rhs(band[0], seed=0)
    with pytest.raises(UnknownBackendError, match="bogus"):
        api.solve_batch(band[1], np.stack([b, b], 1), backend="bogus")


def test_jax_backend_rejects_pallas_options(band):
    b = random_rhs(band[0], seed=0)
    with pytest.raises(BackendOptionsError, match="placement"):
        api.solve_batch(band[1], np.stack([b, b], 1), backend="jax",
                        placement="blocked")


def test_infeasible_blocked_placement_rejected(ckt, small_row_blocks):
    with pytest.raises(PlacementInfeasibleError, match="infeasible"):
        ops.resolve_placement(ckt[1], 8, placement="blocked")


def test_robust_solver_rejects_unknown_backend(band):
    with pytest.raises(UnknownBackendError, match="bogus"):
        RobustSolver(band[1], band[0], backend="bogus")


# ========================================================== CSR validation
def _lower(n=6, seed=0):
    rng = np.random.default_rng(seed)
    rows = list(range(1, n))
    cols = [0] * (n - 1)
    return from_coo(n, rows, cols, rng.standard_normal(n - 1),
                    rng.standard_normal(n) + 3.0, name="probe")


def test_csr_zero_diagonal_named(band):
    mat = _lower()
    mat.values[mat.rowptr[3] - 1] = 0.0  # row 2's diagonal (stored last)
    with pytest.raises(MatrixValidationError, match=r"'probe', row 2.*zero"):
        mat.validate()


def test_csr_super_diagonal_named():
    bad = TriCSR(n=2, rowptr=np.array([0, 2, 3]),
                 colidx=np.array([1, 0, 1]), values=np.ones(3), name="sup")
    with pytest.raises(MatrixValidationError, match=r"'sup'.*super-diagonal"):
        bad.validate()


def test_csr_missing_diagonal_named():
    bad = TriCSR(n=2, rowptr=np.array([0, 1, 1]), colidx=np.array([0]),
                 values=np.ones(1), name="gap")
    with pytest.raises(MatrixValidationError, match=r"'gap', row 1.*missing"):
        bad.validate()


def test_csr_diag_position_named():
    bad = TriCSR(n=2, rowptr=np.array([0, 1, 3]),
                 colidx=np.array([0, 1, 0]), values=np.ones(3), name="pos")
    with pytest.raises(MatrixValidationError, match=r"'pos'.*stored last"):
        bad.validate()


def test_csr_unsorted_columns_named():
    bad = TriCSR(n=3, rowptr=np.array([0, 1, 2, 5]),
                 colidx=np.array([0, 1, 1, 0, 2]), values=np.ones(5),
                 name="uns")
    with pytest.raises(MatrixValidationError,
                       match=r"'uns', row 2.*unsorted"):
        bad.validate()


def test_from_coo_rejects_diagonal_entry():
    with pytest.raises(MatrixValidationError, match="strictly lower"):
        from_coo(3, [1, 2], [1, 0], [1.0, 1.0], np.ones(3), name="coo")


def test_csr_validation_survives_optimized_mode():
    """The structured checks are not ``assert``s: alive under python -O."""
    code = (
        "import sys; sys.path.insert(0, 'src')\n"
        "import numpy as np\n"
        "from repro.core.csr import TriCSR\n"
        "from repro.core.errors import MatrixValidationError\n"
        "bad = TriCSR(n=2, rowptr=np.array([0, 1, 1]),\n"
        "             colidx=np.array([0]), values=np.ones(1), name='opt')\n"
        "try:\n"
        "    bad.validate()\n"
        "except MatrixValidationError as e:\n"
        "    assert 'opt' in str(e); print('CAUGHT')\n"
    )
    out = subprocess.run([sys.executable, "-O", "-c", code],
                         capture_output=True, text=True,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0 and "CAUGHT" in out.stdout, out.stderr


# ======================================================= RobustSolver: happy
def test_robust_solve_matches_oracle(band):
    mat, prog = band
    rs = api.robust_solver(prog, mat, backend="jax")
    b = random_rhs(mat, seed=7)
    np.testing.assert_allclose(rs(b), serial_solve(mat, b), **TOL)
    assert rs.last_stage == "jax" and rs.last_incidents == []
    B = np.stack([random_rhs(mat, seed=s) for s in range(3)], axis=1)
    X = rs.solve(B)
    assert X.shape == (mat.n, 3)
    assert relative_residual(mat, X, B) < 1e-5


def test_ladder_entry_rungs(band):
    mat, prog = band
    assert RobustSolver(prog, mat, backend="numpy").ladder == \
        ("numpy", "reference")
    assert RobustSolver(prog, mat, backend="jax").ladder == \
        ("jax", "numpy", "reference")
    assert RobustSolver(prog, backend="jax").ladder == ("jax", "numpy")
    assert RobustSolver(prog, mat, backend="pallas").ladder == LADDER


@pytest.mark.parametrize("stage", ["jax", "numpy", "reference"])
def test_every_forced_stage_matches_oracle(band, stage):
    """Each rung alone returns the numpy-oracle answer (degradation-safe)."""
    mat, prog = band
    rs = RobustSolver(prog, mat, ladder=(stage,))
    b = random_rhs(mat, seed=11)
    np.testing.assert_allclose(rs(b), serial_solve(mat, b), **TOL)
    assert rs.last_stage == stage


# ================================================= RobustSolver: degradation
def test_build_failure_degrades_with_incident(ckt, small_row_blocks):
    """An infeasible blocked placement degrades; the incident names it (the
    hub DAG has no window once its stream spans several blocks)."""
    mat, prog = ckt
    rs = RobustSolver(prog, mat, ladder=("pallas-blocked", "jax"))
    b = random_rhs(mat, seed=2)
    np.testing.assert_allclose(rs(b), serial_solve(mat, b), **TOL)
    assert rs.last_stage == "jax"
    (inc,) = [i for i in rs.last_incidents if i.stage == "pallas-blocked"]
    assert inc.kind == "build-failed" and "infeasible" in inc.message
    rs.solve(b)  # rung stays disabled: no repeated build attempt
    assert rs.last_incidents == []


def test_corrupt_program_degrades_to_reference(band):
    """Value-plane damage fails residual on every program rung; the
    reference rung (direct CSR solve) still returns the *correct* x."""
    mat, prog = band
    bad = FaultInjector(3).corrupt_stream(prog, k=3, mode="scale")
    rs = RobustSolver(bad, mat, verify=False, ladder=("jax", "numpy",
                                                      "reference"))
    b = random_rhs(mat, seed=5)
    np.testing.assert_allclose(rs(b), serial_solve(mat, b), **TOL)
    assert rs.last_stage == "reference"
    assert [(i.stage, i.kind) for i in rs.last_incidents] == \
        [("jax", "residual"), ("numpy", "residual")]


def test_exhausted_ladder_raises_with_incident_trail(band):
    mat, prog = band
    bad = FaultInjector(3).corrupt_stream(prog, k=3, mode="scale")
    rs = RobustSolver(bad, mat, verify=False, ladder=("jax", "numpy"))
    with pytest.raises(NumericalHealthError, match="all ladder stages") as ei:
        rs.solve(random_rhs(mat, seed=5))
    trail = ei.value.detail["incidents"]
    assert [t["kind"] for t in trail] == ["residual", "residual"]


def test_stage_deadline_disables_rung(band):
    mat, prog = band
    ticks = iter([0.0, 10.0,   # jax rung: elapsed 10s > deadline
                  10.0, 10.1,  # numpy rung: 0.1s, fine
                  20.0, 20.1])  # second solve goes straight to numpy
    rs = RobustSolver(prog, mat, stage_deadline_s=1.0,
                      clock=lambda: next(ticks),
                      ladder=("jax", "numpy"))
    b = random_rhs(mat, seed=9)
    np.testing.assert_allclose(rs(b), serial_solve(mat, b), **TOL)
    assert rs.last_stage == "numpy"
    assert [i.kind for i in rs.last_incidents] == ["deadline"]
    rs.solve(b)  # "jax" now persistently disabled
    assert rs.last_stage == "numpy" and rs.last_incidents == []


def test_bounded_retry_then_success(band):
    mat, prog = band
    calls = {"n": 0}

    class Flaky(RobustSolver):
        def _solver_for(self, stage, batch):
            inner = super()._solver_for(stage, batch)

            def flaky(b):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise RuntimeError("transient lane fault")
                return inner(b)
            return flaky

    rs = Flaky(prog, mat, max_retries=1, ladder=("jax",))
    b = random_rhs(mat, seed=13)
    np.testing.assert_allclose(rs(b), serial_solve(mat, b), **TOL)
    assert calls["n"] == 2 and rs.last_stage == "jax"
    (inc,) = rs.last_incidents
    assert (inc.kind, inc.attempt, inc.error) == \
        ("exception", 1, "RuntimeError")


def test_retries_are_bounded(band):
    mat, prog = band

    class Broken(RobustSolver):
        def _solver_for(self, stage, batch):
            def boom(b):
                raise RuntimeError("permanent fault")
            return boom

    rs = Broken(prog, mat, max_retries=1, ladder=("jax",))
    with pytest.raises(BackendExecutionError) as ei:
        rs.solve(random_rhs(mat, seed=1))
    assert [t["attempt"] for t in ei.value.detail["incidents"]] == [1, 2]


# ==================================================== RobustSolver: inputs
def test_nonfinite_rhs_rejected(band):
    mat, prog = band
    rs = api.robust_solver(prog, mat)
    bad = random_rhs(mat, seed=0)
    bad[4] = np.nan
    with pytest.raises(NumericalHealthError, match="non-finite"):
        rs(bad)
    bad[4] = np.inf
    with pytest.raises(NumericalHealthError, match="non-finite"):
        rs(bad)


def test_wrong_shape_and_dtype_rejected(band):
    mat, prog = band
    rs = api.robust_solver(prog, mat)
    with pytest.raises(NumericalHealthError, match=r"\[n\] or \[n, B\]"):
        rs(np.zeros(mat.n + 1))
    with pytest.raises(NumericalHealthError, match="not numeric"):
        rs(np.array(["a"] * mat.n, dtype=object))


def test_construction_verifies_program(band):
    bad = FaultInjector(0).corrupt_stream(band[1], k=1, mode="nan")
    with pytest.raises(ProgramCorruptionError, match="non-finite"):
        RobustSolver(bad, band[0])


# ================================================ fault-injection smoke tier
def test_fault_injection_no_silent_wrong_answers(ckt):
    """Every fault class is detected or safely degraded — the PR's bar."""
    trials = run_fault_injection(ckt[0], ckt[1], trials_per_class=2, seed=0)
    assert {t["fault"] for t in trials} == set(FAULT_CLASSES)
    assert not any(t["silent_wrong"] for t in trials), trials
    by_class = {}
    for t in trials:
        by_class.setdefault(t["fault"], []).append(t["detected"])
    # structural and I/O faults are *detected*, never merely degraded
    for fault in ("psum_slot", "blob", "rhs_nan", "rhs_inf"):
        assert all(d != "none" for d in by_class[fault]), by_class[fault]


def test_benchmark_smoke_tier():
    from benchmarks.robust_overhead import run

    rows = run(smoke=True)
    assert rows, "smoke set is empty"
    assert sum(r["silent_wrong"] for r in rows) == 0
    assert {r["fault"] for r in rows} == set(FAULT_CLASSES)
