"""Scheduling-strategy frontier (DESIGN.md §11): correctness + selection.

Every registered strategy must produce a `Program` that passes the full
static verifier and bit-matches the numpy oracle on every executor; the
analytic cost model must be exact (predicted cycles == measured
``stats.cycles``); ``schedule="auto"`` must never be worse than the
paper baseline and must win where the frontier says it does; and the
strategy must be part of the `ProgramCache` identity.  The
``BENCH_schedule.json`` trajectory schema is guarded here too.
"""

import numpy as np
import pytest

from repro.core import api, robust
from repro.core.compiler import strategies
from repro.core.csr import random_rhs, serial_solve
from repro.core.matrices import generate
from repro.core.program import AccelConfig
from repro.core.serve import ProgramCache, pattern_fingerprint
from repro.kernels.sptrsv import ops

ALT_STRATEGIES = [s for s in strategies.STRATEGIES if s != "paper"]
PARITY_SET = ["band_cz", "hub_small"]


# ------------------------------------------------ registry + validation
def test_registry_shape_and_unknown_name():
    assert list(strategies.STRATEGIES) == ["paper", "locality", "cpath",
                                           "eager"]
    with pytest.raises(ValueError, match="unknown schedule strategy"):
        strategies.get("nope")
    with pytest.raises(ValueError, match="options: paper, locality"):
        strategies.get("level")
    with pytest.raises(ValueError, match="unknown schedule strategy"):
        api.compile(generate("hub_small"), schedule="nope")


def test_coarse_dataflow_keeps_single_candidate():
    cfg = AccelConfig(dataflow="coarse", icr=False, psum_cache=False)
    assert strategies.candidate_names(cfg) == ["paper"]
    # auto degrades to the paper schedule rather than erroring
    prog = api.compile(generate("hub_small"), cfg, schedule="auto")
    assert prog.stats.schedule == "paper"


# ------------------------------------------------ per-strategy parity
@pytest.mark.parametrize("name", PARITY_SET)
@pytest.mark.parametrize("strategy", ALT_STRATEGIES)
def test_strategy_verifies_and_matches_oracle(name, strategy):
    mat = generate(name)
    prog = api.compile(mat, schedule=strategy, verify_ir=True)
    robust.verify_program(prog)  # raises on any structural/hazard diag
    assert prog.stats.schedule == strategy
    b = random_rhs(mat, 11)
    np.testing.assert_allclose(api.solve_numpy(prog, b),
                               serial_solve(mat, b), rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("strategy", ALT_STRATEGIES)
def test_strategy_jax_and_pallas_executors_agree(strategy):
    mat = generate("band_cz")
    prog = api.compile(mat, schedule=strategy)
    b = random_rhs(mat, 12)
    ref = api.solve_numpy(prog, b)
    np.testing.assert_allclose(api.solve(prog, b), ref,
                               rtol=1e-5, atol=1e-5)
    xr = ops.solve(prog, b, interpret=True, placement="resident")
    np.testing.assert_allclose(xr, ref, rtol=1e-5, atol=1e-5)
    plan = ops.plan_window(prog)
    assert plan.feasible, plan.reason
    xb = ops.solve(prog, b, interpret=True, placement="blocked")
    np.testing.assert_allclose(xb, ref, rtol=1e-5, atol=1e-5)


# ------------------------------------------------ cost model + auto
def test_auto_cost_model_is_exact_and_never_worse_than_paper():
    for name in ("ckt_fpga", "hub_small", "band_cz"):
        prog = api.compile(generate(name), schedule="auto")
        st = prog.stats
        costs = st.schedule_costs
        assert set(costs) == set(strategies.STRATEGIES)
        assert st.schedule in costs
        assert st.cycles == costs[st.schedule]["cycles"], name
        assert st.cycles <= costs["paper"]["cycles"], name


def test_auto_strictly_wins_on_psum_bound_circuit():
    # list schedulers beat the paper's resume-first order on ckt_fpga
    prog = api.compile(generate("ckt_fpga"), schedule="auto")
    st = prog.stats
    assert st.schedule != "paper"
    assert st.cycles < st.schedule_costs["paper"]["cycles"]
    b = random_rhs(generate("ckt_fpga"), 13)
    np.testing.assert_allclose(api.solve_numpy(prog, b),
                               serial_solve(generate("ckt_fpga"), b),
                               rtol=2e-4, atol=1e-4)


def test_auto_records_selection_pass_and_report():
    prog = api.compile(generate("hub_small"), schedule="auto")
    names = [ps.name for ps in prog.stats.pass_stats]
    assert "strategy_select" in names
    sel = next(ps for ps in prog.stats.pass_stats
               if ps.name == "strategy_select")
    assert sel.metrics["chosen"] == prog.stats.schedule
    assert set(sel.metrics["predicted_cycles"]) == \
        set(strategies.STRATEGIES)
    rep = api.report(prog)
    assert rep["schedule"] == prog.stats.schedule
    assert set(rep["schedule_costs"]) == set(strategies.STRATEGIES)


def test_explicit_strategy_round_trips_serialization(tmp_path):
    prog = api.compile(generate("hub_small"), schedule="locality")
    path = tmp_path / "locality.prog"
    api.save_program(prog, path)
    loaded = api.load_program(path)
    assert loaded.stats.schedule == "locality"
    np.testing.assert_array_equal(loaded.instr, prog.instr)


# ------------------------------------------------ cache-key separation
def test_program_cache_keys_separate_strategies():
    mat = generate("hub_small")
    fp_paper = pattern_fingerprint(mat)
    assert pattern_fingerprint(mat, "paper") == fp_paper  # back-compat
    assert pattern_fingerprint(mat, "locality") != fp_paper
    assert pattern_fingerprint(mat, "locality") != \
        pattern_fingerprint(mat, "eager")

    base = ProgramCache(capacity=2)
    alt = ProgramCache(capacity=2, schedule="locality")
    assert base.get(mat).stats.schedule == "paper"
    assert alt.get(mat).stats.schedule == "locality"


# ------------------------------------------------ SPT208 frontier lint
def _fake_costs(paper: int, locality: int) -> dict:
    return {s: {"strategy": s, "cycles": c, "stall_rows": 0,
                "psum_spills": 0, "planes": 1}
            for s, c in (("paper", paper), ("locality", locality))}


def test_spt208_fires_past_threshold_only():
    from repro.core.analysis import analyze_program

    prog = api.compile(generate("hub_small"))
    prog.stats.schedule = "locality"
    prog.stats.schedule_costs = _fake_costs(paper=100, locality=150)
    assert "SPT208" in analyze_program(prog).codes()
    prog.stats.schedule_costs = _fake_costs(paper=100, locality=105)
    assert "SPT208" not in analyze_program(prog).codes()  # within 10%


def test_lint_cli_frontier_flags_paper_on_circuit(capsys):
    from scripts.lint_program import main

    rc = main(["--matrix", "ckt_rajat04", "--schedule", "paper",
               "--frontier"])
    out = capsys.readouterr().out
    assert rc == 0  # warn-severity only
    assert "SPT208" in out


# ------------------------------------------------ bench smoke + schema
def test_schedule_frontier_smoke(capsys):
    from benchmarks.schedule_frontier import main

    main(["--smoke"])
    out = capsys.readouterr().out
    assert "smoke" in out and "never worse" in out


def test_bench_schedule_trajectory_schema():
    from scripts.check_bench import check_schedule

    problems = check_schedule()
    assert not problems, "\n".join(problems)
