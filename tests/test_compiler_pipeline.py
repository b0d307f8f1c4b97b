"""Staged compiler pipeline: legacy equivalence + per-pass contracts.

The pipeline (`core/compiler/`) must reproduce the frozen pre-refactor
monolith (`tests/legacy_schedule.py`) bit-for-bit: identical packed
instruction stream, value stream, row envelopes and stats on the bundled
matrix suite — a fast subset in tier-1, the full suite marked ``slow``.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))  # tests/legacy_schedule.py

import legacy_schedule  # noqa: E402

from repro.core import compiler  # noqa: E402
from repro.core.compiler import ir, sched  # noqa: E402
from repro.core.frontends.sptrsv import lower_tri  # noqa: E402
from repro.core.matrices import generate, suite_names  # noqa: E402
from repro.core.program import MAX_SLOT, SLOT_BITS, AccelConfig  # noqa: E402
from repro.core.schedule import allocate_nodes, compile_program  # noqa: E402

FAST_SET = ["band_cz", "ckt_rajat04", "chem_bp", "wide_c36", "hub_small"]
CFG_VARIANTS = [
    AccelConfig(),
    AccelConfig(psum_cache=False),
    AccelConfig(icr=False),
    AccelConfig(alloc="roundrobin"),
    AccelConfig(psum_words=2),
    AccelConfig(dataflow="coarse", icr=False, psum_cache=False),
]


def _stats_dict(st):
    d = dataclasses.asdict(st)
    d.pop("compile_seconds")        # timing — not part of the contract
    d.pop("pass_stats")             # pipeline-only observability
    per_cu = d.pop("per_cu_edges")
    return d, per_cu


def assert_programs_identical(a, b, ctx=""):
    assert np.array_equal(a.instr, b.instr), f"{ctx}: instr differs"
    assert np.array_equal(a.val_idx, b.val_idx), f"{ctx}: val_idx differs"
    assert np.array_equal(a.stream, b.stream), f"{ctx}: stream differs"
    assert np.array_equal(a.row_lo, b.row_lo), f"{ctx}: row_lo differs"
    assert np.array_equal(a.row_hi, b.row_hi), f"{ctx}: row_hi differs"
    assert a.num_slots == b.num_slots, ctx
    da, pa = _stats_dict(a.stats)
    db, pb = _stats_dict(b.stats)
    diff = {k: (da[k], db[k]) for k in da if da[k] != db[k]}
    assert not diff, f"{ctx}: stats differ: {diff}"
    assert np.array_equal(pa, pb), f"{ctx}: per_cu_edges differ"


@pytest.mark.parametrize("name", FAST_SET)
def test_pipeline_matches_legacy(name):
    mat = generate(name)
    for cfg in CFG_VARIANTS:
        legacy = legacy_schedule.compile_program(mat, cfg)
        staged = compile_program(mat, cfg)
        assert_programs_identical(legacy, staged, f"{name}/{cfg.dataflow}")


@pytest.mark.slow
def test_pipeline_matches_legacy_full_suite():
    """Acceptance: identical Program.instr/stats on the FULL bundled suite,
    up to band_huge64k's 65,536 rows.  Past it is only hpcg_symgs48, the
    benchmark's HPCG grid, over a minute per compile on a CPU; hpcg_8 is the
    same generator at a test size."""
    for name in suite_names(max_n=65_536):
        mat = generate(name)
        assert_programs_identical(
            legacy_schedule.compile_program(mat),
            compile_program(mat),
            name,
        )


def test_pipeline_records_all_passes():
    prog = compile_program(generate("band_cz"))
    names = [p.name for p in prog.stats.pass_stats]
    assert names == list(compiler.PASS_NAMES)
    by = {p.name: p for p in prog.stats.pass_stats}
    assert by["partition"].metrics["edges"] == prog.stats.nnz - prog.n
    assert by["psum_schedule"].metrics["hardware_cycles"] == prog.stats.cycles
    assert by["stall_elide"].metrics["emitted_cycles"] == prog.cycles
    assert by["pack_emit"].metrics["instr_bytes"] == prog.instr_bytes()
    assert by["icr_reorder"].metrics["reuse_events"] == prog.stats.reuse_events
    assert all(p.seconds >= 0 for p in prog.stats.pass_stats)


def test_pass_boundaries_compose():
    """Each stage's IR output feeds the next; spot-check the invariants."""
    mat = generate("ckt_rajat04")
    cfg = AccelConfig()
    dag = lower_tri(mat)
    pir = compiler.partition.run(dag)
    assert [len(c) for c in pir.consumers] == \
        np.bincount(dag.src, minlength=dag.n).tolist()
    air = compiler.assign.run(pir, cfg)
    assert sorted(i for ts in air.task_lists for i in ts) == list(range(mat.n))
    assert all(air.owner[i] == c
               for c, ts in enumerate(air.task_lists) for i in ts)
    sir = compiler.sched.run(air, cfg)
    assert sir.ops.shape[0] == sir.stats.cycles  # dense: incl. stall rows
    eir = compiler.elide.run(sir)
    assert eir.ops.shape[0] == sir.stats.emitted_cycles <= sir.stats.cycles
    assert np.all(eir.ops.max(axis=1) > 0)       # no all-NOP row survives
    prog = compiler.emit.run(eir, cfg)
    assert prog.cycles == eir.ops.shape[0]


def test_allocate_nodes_wrapper_unchanged():
    mat = generate("chem_bp")
    tasks = allocate_nodes(mat, AccelConfig())
    legacy = legacy_schedule.allocate_nodes(mat, AccelConfig())
    assert tasks == legacy


def test_frontend_contract_violations_rejected():
    bad_src = ir.ComputeDag("bad", 2, np.array([0, 1, 1]),
                            np.array([1]), np.array([1.0]), np.ones(2))
    with pytest.raises(ValueError, match="smaller node id"):
        bad_src.validate()
    zero_scale = ir.ComputeDag("bad", 2, np.array([0, 0, 1]),
                               np.array([0]), np.array([1.0]),
                               np.array([1.0, 0.0]))
    with pytest.raises(ValueError, match="finite and non-zero"):
        zero_scale.validate()
    dup = ir.ComputeDag("bad", 3, np.array([0, 0, 0, 2]),
                        np.array([0, 0]), np.ones(2), np.ones(3))
    with pytest.raises(ValueError, match="ascending"):
        dup.validate()


def test_psum_overflow_cap_derived_from_slot_field():
    """Satellite: the overflow-slot cap comes from the packed slot width
    (8 bits ⇒ 255 incl. overflow) and the error names the workload + CU."""
    assert sched.MAX_PSUM_SLOT == MAX_SLOT == (1 << SLOT_BITS) - 1
    cu = sched._CU(7, "band_cz", [0], psum_words=8)
    cu.free_over.clear()
    cu.next_over = MAX_SLOT  # last representable slot id: still fine
    assert cu.peek_over_slot() == MAX_SLOT
    cu.next_over = MAX_SLOT + 1
    with pytest.raises(RuntimeError) as exc:
        cu.peek_over_slot()
    msg = str(exc.value)
    assert "band_cz" in msg and "CU 7" in msg and str(MAX_SLOT) in msg
