"""Static-analysis subsystem tests (DESIGN.md §8).

Three layers under test:

1. per-pass contract verifiers (`core/analysis/contracts.py`) wired into
   `compile_dag(verify_ir=True)` — a broken invariant must raise
   `IRValidationError` naming the guilty pass;
2. the schedule hazard/race detector (`core/analysis/hazards.py`) — every
   IR-level fault class (`core.robust.IR_FAULT_CLASSES`) must fire its
   expected diagnostic code, and every suite matrix must verify clean at
   the default configuration;
3. the performance linter (`core/analysis/perf.py`) — SPT2xx lints fire
   on the workloads known to exhibit the smells.

The benchmark smoke guard (`benchmarks/analysis_overhead.py --smoke`)
runs here too, so tier-1 keeps the fault-injection acceptance bar green.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import api, matrices
from repro.core.analysis import (
    CODES,
    SEV_ERROR,
    AnalysisReport,
    Diagnostic,
    analyze_program,
    lint_program,
    program_diagnostics,
    verify_assign,
    verify_emit,
    verify_frontend,
    verify_packed_program,
    verify_partition,
    verify_schedule,
)
from repro.core.compiler import assign, elide, emit, partition, sched
from repro.core.errors import IRValidationError, ProgramCorruptionError
from repro.core.frontends.sptrsv import lower_tri
from repro.core.program import AccelConfig
from repro.core.robust import (
    IR_FAULT_CLASSES,
    FaultInjector,
    run_ir_fault_injection,
    verify_program,
)

# small matrices spanning the structural spectrum (band / circuit / wide /
# hub); ckt_rajat04 is the one with live psum slot traffic, so every IR
# fault class is applicable there
FAST_SET = ["band_cz", "ckt_rajat04", "chem_bp", "wide_c36", "hub_small"]
FULL_MATRIX = "ckt_rajat04"


@pytest.fixture(scope="module")
def pipeline():
    """All staged IRs of FULL_MATRIX at the default config."""
    cfg = AccelConfig()
    dag = lower_tri(matrices.generate(FULL_MATRIX))
    pir = partition.run(dag)
    air = assign.run(pir, cfg)
    sir = sched.run(air, cfg)
    eir = elide.run(sir)
    prog = emit.run(eir, cfg, planes=None)
    return cfg, dag, pir, air, sir, eir, prog


# ------------------------------------------------------------ diagnostics
def test_code_registry_is_well_formed():
    for code, title in CODES.items():
        assert code.startswith("SPT") and len(code) == 6, code
        assert code[3] in "123", (
            f"{code}: 1xx correctness / 2xx perf / 3xx serving only")
        assert title


def test_diagnostic_rejects_unknown_code_and_severity():
    with pytest.raises(ValueError):
        Diagnostic(code="SPT999", severity=SEV_ERROR, message="x")
    with pytest.raises(ValueError):
        Diagnostic(code="SPT110", severity="fatal", message="x")


def test_report_render_and_json_roundtrip():
    d = Diagnostic(code="SPT110", severity=SEV_ERROR, message="row 3 never "
                   "finalized", pass_name="psum_schedule", node=3)
    rep = AnalysisReport(name="unit", meta={"n": 4}).extend([d])
    assert not rep.ok() and rep.codes() == {"SPT110"}
    text = rep.render()
    assert "SPT110" in text and "psum_schedule" in text
    blob = rep.to_json()
    import json

    back = json.loads(blob)
    assert back["name"] == "unit"
    assert back["diagnostics"][0]["code"] == "SPT110"
    assert back["diagnostics"][0]["node"] == 3


# ------------------------------------------------- clean-compile contract
@pytest.mark.parametrize("name", FAST_SET)
def test_clean_compile_verifies(name):
    prog = api.compile(matrices.generate(name), verify_ir=True)
    entries = [ps for ps in prog.stats.pass_stats if ps.name == "verify_ir"]
    assert len(entries) == 1
    assert entries[0].metrics["stages_verified"] == 6
    assert entries[0].seconds >= 0.0


def test_every_stage_verifies_clean(pipeline):
    cfg, dag, pir, air, sir, eir, prog = pipeline
    assert verify_frontend(dag) == []
    assert verify_partition(pir) == []
    assert verify_assign(air, cfg) == []
    assert verify_schedule(sir, air, cfg) == []
    assert verify_emit(eir, sir) == []
    assert verify_packed_program(prog, eir, cfg) == []


@pytest.mark.parametrize("cfg", [
    AccelConfig(num_cus=8, psum_words=4),
    AccelConfig(alloc="roundrobin"),
    AccelConfig(icr=False, psum_cache=False),
], ids=["small", "roundrobin", "no_icr_no_cache"])
def test_config_variants_verify_clean(cfg):
    for name in ["ckt_rajat04", "hub_small"]:
        api.compile(matrices.generate(name), cfg, verify_ir=True)


def test_suite_sweep_zero_diagnostics():
    """Every suite matrix (n <= 3000) compiles verified and lints with
    zero error diagnostics at the default configuration."""
    names = matrices.suite_names(max_n=3000)
    assert len(names) >= 17
    for name in names:
        prog = api.compile(matrices.generate(name), verify_ir=True)
        report = analyze_program(prog)
        assert report.errors == [], f"{name}: {report.render()}"


# ------------------------------------------------- IR fault injection
@pytest.mark.parametrize("fault", IR_FAULT_CLASSES)
def test_ir_fault_fires_expected_code(fault):
    mat = matrices.generate(FULL_MATRIX)
    (r,) = run_ir_fault_injection(mat, seed=3, classes=(fault,))
    assert r["applicable"], f"{fault} must be applicable on {FULL_MATRIX}"
    assert r["caught"], (f"{fault}: expected {r['expected_code']}, "
                         f"verifier fired {r['fired_codes']}")


def test_ir_fault_injection_seed_sweep():
    mat = matrices.generate(FULL_MATRIX)
    for seed in range(5):
        for r in run_ir_fault_injection(mat, seed=seed):
            assert r["applicable"] and r["caught"], r


def test_verify_ir_names_frontend_on_dag_fault():
    dag = lower_tri(matrices.generate(FULL_MATRIX))
    bad = FaultInjector(0).corrupt_dag(dag)
    with pytest.raises(IRValidationError) as exc:
        api.compile_dag(bad, verify_ir=True)
    assert "frontend" in str(exc.value)
    assert exc.value.detail["pass"] == "frontend"
    assert exc.value.detail["code"] == "SPT118"


def test_verify_ir_names_guilty_pass_on_schedule_fault(monkeypatch):
    """A scheduler bug (simulated by mutating its output) is blamed on
    psum_schedule — not discovered later as a generic corrupt program."""
    inj = FaultInjector(1)
    real_run = sched.run

    def bad_run(air, cfg):
        return inj.corrupt_schedule(real_run(air, cfg), "raw")

    monkeypatch.setattr(sched, "run", bad_run)
    with pytest.raises(IRValidationError) as exc:
        api.compile(matrices.generate(FULL_MATRIX), verify_ir=True)
    assert exc.value.detail["pass"] == "psum_schedule"
    assert exc.value.detail["code"] in ("SPT111", "SPT117")


def test_unverified_compile_ignores_ir_faults(monkeypatch):
    """Without verify_ir the pipeline stays permissive: the same mutation
    compiles (garbage in, packed garbage out) and only the packed-program
    checks can complain."""
    inj = FaultInjector(1)
    real_run = sched.run

    def bad_run(air, cfg):
        return inj.corrupt_schedule(real_run(air, cfg), "raw")

    monkeypatch.setattr(sched, "run", bad_run)
    prog = api.compile(matrices.generate(FULL_MATRIX))
    assert prog.cycles > 0


# ------------------------------------------------- verify_program dedup
def test_verify_program_raises_first_analyzer_error(pipeline):
    *_, prog = pipeline
    from repro.core.robust import _copy_program

    bad = _copy_program(prog)
    bad.val_idx[0, 0] = np.int32(bad.stream.size + 11)
    diags = program_diagnostics(bad)
    first = next(d for d in diags if d.severity == SEV_ERROR)
    with pytest.raises(ProgramCorruptionError) as exc:
        verify_program(bad)
    assert str(exc.value) == f"program integrity: {first.message}"
    assert exc.value.detail["code"] == first.code


def test_verify_program_clean(pipeline):
    *_, prog = pipeline
    verify_program(prog)  # must not raise
    assert program_diagnostics(prog) == []


# ------------------------------------------------------------ perf linter
def test_linter_flags_hub_imbalance():
    prog = api.compile(matrices.generate("hub_small"))
    codes = {d.code for d in lint_program(prog)}
    assert "SPT201" in codes  # load CV blowup on the hub row
    assert "SPT206" in codes  # utilization collapse


def test_linter_flags_psum_pressure():
    prog = api.compile(matrices.generate(FULL_MATRIX))
    codes = {d.code for d in lint_program(prog)}
    assert "SPT202" in codes  # emergency psum parks escape to overflow


def test_linter_silent_on_balanced_band():
    prog = api.compile(matrices.generate("band_cz"))
    assert lint_program(prog) == []


@pytest.mark.parametrize("name,block,warns", [
    ("band_cz", 1024, False),       # the window slides over its blocks
    ("ckt_rajat04", 1024, True),    # hub rows stay live across all blocks
    ("ckt_rajat04", None, False),   # one block: the stream is its window
])
def test_linter_judges_blocked_windows_per_row_block(name, block, warns,
                                                     monkeypatch):
    """SPT205 judges the blocked kernel's stream as it runs, a window per
    block of `kernel.ROW_BLOCK` entries: a hub DAG whose stream spans
    several blocks has no window, and one whose stream fits a block is its
    own window.  Small programs span several blocks of 1,024 entries."""
    from repro.kernels.sptrsv import ops

    if block is not None:
        monkeypatch.setattr(ops, "ROW_BLOCK", block)
    prog = api.compile(matrices.generate(name))
    assert (int((prog.opcode != 0).sum()) > ops.ROW_BLOCK) == \
        (block is not None)
    assert ("SPT205" in {d.code for d in lint_program(prog)}) == warns


def test_analyze_program_report_shape(pipeline):
    *_, prog = pipeline
    report = analyze_program(prog)
    assert report.errors == []
    assert report.meta["artifact"] == "program"
    assert set(report.codes()) <= set(CODES)


# ---------------------------------------------------- benchmark smoke tier
def test_analysis_benchmark_smoke(capsys):
    from benchmarks.analysis_overhead import main

    main(["--smoke"])  # asserts internally: all faults caught, 0 errors
    out = capsys.readouterr().out
    assert "all caught" in out


# ------------------------------------------------- deterministic sweep
# (the hypothesis-driven version lives in test_analysis_property.py)
def test_random_lower_tri_verifies_clean():
    """Random well-formed lower-triangular systems compile with verify_ir
    and analyze with zero error diagnostics."""
    from repro.core.csr import from_coo

    for seed in range(6):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(2, 48))
        rows, cols = [], []
        for i in range(1, n):
            m = rng.random(i) < 0.3
            for j in np.nonzero(m)[0]:
                rows.append(i)
                cols.append(int(j))
        vals = rng.uniform(-1, 1, len(rows))
        diag = rng.uniform(1.0, 2.0, n)
        mat = from_coo(n, rows, cols, vals, diag, name=f"rnd_an_{seed}")
        prog = api.compile(mat, verify_ir=True)
        assert analyze_program(prog, lint=False).ok()
