"""Property-based resilience tests (hypothesis; skipped if unavailable).

A random interleaving of solve requests, injected backend faults, clock
advances, and overload bursts is replayed against a resilient
SolveService.  The invariants, for EVERY interleaving:

  * every ticket terminates (no deadlock): completed, typed-failed, or
    shed — never left pending after a drain;
  * every completed non-shed ticket is bit-identical to the
    stage-matched oracle (execute_numpy for entry-rung flushes,
    serial_solve for degraded reference flushes) — zero silent wrong
    answers;
  * every failed ticket carries a typed RobustnessError;
  * accounting closes: requests == completed + failed + shed.
"""

import numpy as np
import pytest

hyp = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.csr import serial_solve
from repro.core.errors import RobustnessError
from repro.core.executor import execute_numpy
from repro.core.matrices import banded
from repro.core.resilience import (
    AdmissionConfig,
    BreakerConfig,
    ResilienceConfig,
    RetryPolicy,
)
from repro.core.compiler import compile_program
from repro.core.serve import ManualClock, ProgramCache, SolveService

_MATS = {
    "a": banded(40, 6, 0.6, 101, "prop_res_a"),
    "b": banded(32, 4, 0.5, 102, "prop_res_b"),
}
_PROGS = {mid: compile_program(m) for mid, m in _MATS.items()}

# (tenant, n_cols, fault, advance_s, rhs_seed) — fault applies to the
# entry ("numpy") rung of the flush that next consumes the solver.
_STEP = st.tuples(
    st.sampled_from(sorted(_MATS)),
    st.integers(min_value=1, max_value=5),
    st.sampled_from(["none", "none", "exc", "exc-exc", "nan"]),
    st.sampled_from([0.0, 0.05, 0.2, 0.6, 1.5]),
    st.integers(min_value=0, max_value=2**16),
)


def _build_service(fault_feed):
    clock = ManualClock()
    res = ResilienceConfig(
        retry=RetryPolicy(max_retries=1, base_delay_s=0.001, jitter=0.0),
        breaker=BreakerConfig(window_s=30.0, min_samples=3,
                              failure_threshold=0.6, cooldown_s=2.0),
        admission=AdmissionConfig(max_pending_per_matrix=8,
                                  max_pending_total=12),
    )
    svc = SolveService(ProgramCache(), max_batch=3, max_delay=0.5,
                       clock=clock, backend="numpy", resilience=res)
    for mid, m in _MATS.items():
        svc.register(mid, m)

    orig = svc._stage_solver

    def wrapped(stage, prog, k, mat):
        fn = orig(stage, prog, k, mat)
        if stage != "numpy":
            return fn

        def chaotic(bmat):
            action = fault_feed.pop(0) if fault_feed else "none"
            if action.startswith("exc"):
                if action == "exc-exc":  # survives one retry too
                    fault_feed.insert(0, "exc")
                raise RuntimeError("injected backend fault")
            x = np.asarray(fn(bmat))
            if action == "nan":
                return np.full_like(x, np.nan)
            return x
        return chaotic

    svc._stage_solver = wrapped
    return svc, clock


def _check_ticket(svc, ticket, rhs):
    if ticket.shed:
        with pytest.raises(RobustnessError):
            ticket.result()
        return "shed"
    assert ticket.done, "ticket left pending after drain (deadlock)"
    if ticket.failed:
        assert isinstance(ticket.error, RobustnessError)
        return "failed"
    flush_by_index = {r.index: r for r in svc.stats.flushes if r.index >= 0}
    stages = {flush_by_index[i].stage for i in ticket.flush_indices}
    got = np.asarray(ticket.result())
    mid = ticket.matrix_id
    if stages == {"numpy"}:
        want = np.asarray(execute_numpy(_PROGS[mid], rhs))
    elif stages == {"reference"}:
        bm = np.asarray(rhs, dtype=np.float64)
        cols = bm[:, None] if bm.ndim == 1 else bm
        want = np.stack([serial_solve(_MATS[mid], cols[:, j])
                         for j in range(cols.shape[1])], axis=1)
        if rhs.ndim == 1:
            want = want[:, 0]
    else:  # mixed-stage wide ticket: weaker residual bound
        dense = _MATS[mid].to_dense()
        cols = got.reshape(dense.shape[0], -1).astype(np.float64)
        rcols = rhs.reshape(dense.shape[0], -1).astype(np.float64)
        for j in range(cols.shape[1]):
            r = rcols[:, j] - dense @ cols[:, j]
            denom = max(float(np.linalg.norm(rcols[:, j])), 1e-30)
            assert float(np.linalg.norm(r)) / denom <= 1e-3
        return "completed"
    np.testing.assert_array_equal(got, want)
    return "completed"


@settings(max_examples=60, deadline=None, derandomize=True)
@given(steps=st.lists(_STEP, min_size=1, max_size=12))
def test_random_fault_interleavings_never_silently_wrong(steps):
    fault_feed = [f for (_, _, f, _, _) in steps]
    svc, clock = _build_service(list(fault_feed))
    tickets = []
    for (mid, k, _fault, adv, rhs_seed) in steps:
        rng = np.random.default_rng(rhs_seed)
        n = _MATS[mid].n
        rhs = (rng.standard_normal(n) if k == 1
               else rng.standard_normal((n, k))).astype(np.float32)
        tickets.append((svc.submit(mid, rhs), rhs))
        clock.advance(adv)
        svc.pump()
    clock.advance(10.0)
    svc.pump()
    svc.drain()

    outcomes = {"completed": 0, "failed": 0, "shed": 0}
    for ticket, rhs in tickets:
        outcomes[_check_ticket(svc, ticket, rhs)] += 1
    assert sum(outcomes.values()) == len(steps)
    st_ = svc.stats
    assert st_.requests == len(steps)  # shed requests are still requests
    assert outcomes["shed"] == st_.requests_shed
    assert st_.failed_flushes == 0 or outcomes["failed"] > 0


@settings(max_examples=25, deadline=None, derandomize=True)
@given(steps=st.lists(_STEP, min_size=1, max_size=10),
       seed=st.integers(min_value=0, max_value=7))
def test_interleaving_is_deterministic(steps, seed):
    """The same interleaving replayed twice gives identical outcomes,
    stats, and bit-identical answers — resilience adds no hidden
    nondeterminism (no wall-clock reads, seeded jitter only)."""
    runs = []
    for _ in range(2):
        fault_feed = [f for (_, _, f, _, _) in steps]
        svc, clock = _build_service(list(fault_feed))
        tickets = []
        for (mid, k, _fault, adv, rhs_seed) in steps:
            rng = np.random.default_rng(rhs_seed + seed)
            n = _MATS[mid].n
            rhs = (rng.standard_normal(n) if k == 1
                   else rng.standard_normal((n, k))).astype(np.float32)
            tickets.append(svc.submit(mid, rhs))
            clock.advance(adv)
            svc.pump()
        clock.advance(10.0)
        svc.pump()
        svc.drain()
        outs = []
        for t in tickets:
            if t.shed:
                outs.append(("shed", None))
            elif t.failed:
                outs.append(("failed", type(t.error).__name__))
            else:
                outs.append(("ok", np.asarray(t.result()).tobytes()))
        stats = svc.stats.to_dict()
        stats.pop("flushes", None)
        stats.pop("cache", None)  # compile_seconds is real wall time
        runs.append((outs, stats, [i.kind for i in svc.incidents]))
    assert runs[0] == runs[1]
