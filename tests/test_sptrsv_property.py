"""Property-based tests (hypothesis) on the compiler/executor invariants."""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import api
from repro.core.csr import from_coo, serial_solve
from repro.core.program import AccelConfig
from repro.core.compiler import compile_program


@st.composite
def random_triangular(draw):
    n = draw(st.integers(min_value=2, max_value=90))
    seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
    density = draw(st.floats(min_value=0.0, max_value=0.5))
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for i in range(1, n):
        m = rng.random(i) < density
        for j in np.nonzero(m)[0]:
            rows.append(i)
            cols.append(int(j))
    vals = rng.uniform(-1, 1, len(rows))
    diag = rng.uniform(1.0, 2.0, n) * rng.choice([-1.0, 1.0], n)
    return from_coo(n, rows, cols, vals, diag, name=f"hyp_{seed}")


@st.composite
def accel_config(draw):
    return AccelConfig(
        num_cus=draw(st.sampled_from([4, 8, 16, 64])),
        psum_words=draw(st.sampled_from([1, 2, 8])),
        xi_words=draw(st.sampled_from([8, 64])),
        num_banks=draw(st.sampled_from([8, 64])),
        icr=draw(st.booleans()),
        psum_cache=draw(st.booleans()),
        alloc=draw(st.sampled_from(["least_edges", "roundrobin"])),
        icr_window=draw(st.sampled_from([2, 16])),
    )


@settings(max_examples=40, deadline=None)
@given(random_triangular(), accel_config(), st.integers(0, 1000))
def test_executor_matches_oracle(mat, cfg, bseed):
    """For ANY matrix and ANY hardware config the compiled program must
    reproduce the serial solve — the fundamental system invariant."""
    prog = compile_program(mat, cfg)
    rng = np.random.default_rng(bseed)
    b = rng.standard_normal(mat.n)
    got = api.solve_numpy(prog, b)
    ref = serial_solve(mat, b)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


@settings(max_examples=40, deadline=None)
@given(random_triangular(), accel_config())
def test_schedule_invariants(mat, cfg):
    prog = compile_program(mat, cfg)
    st_ = prog.stats
    # every op exactly once
    assert st_.exec_edges == mat.nnz - mat.n
    assert st_.exec_finals == mat.n
    # cycle count bounded below by work/P and above by the serial bound
    assert st_.cycles >= mat.nnz / cfg.num_cus - 1
    assert st_.cycles <= 2 * mat.nnz + 64 * mat.n + 4096
    # stream memory consumed exactly once per op, in order
    assert len(prog.stream) == mat.nnz
    vi = prog.val_idx[prog.opcode > 0]
    assert sorted(vi.tolist()) == list(range(mat.nnz))


@settings(max_examples=25, deadline=None)
@given(random_triangular())
def test_causality(mat):
    """An edge may only read x[j] strictly after node j finalizes."""
    prog = api.compile(mat)
    solve_cycle = {}
    for t in range(prog.cycles):
        for c in range(prog.num_cus):
            if prog.opcode[t, c] == 2:
                solve_cycle[int(prog.out_idx[t, c])] = t
    for t in range(prog.cycles):
        for c in range(prog.num_cus):
            if prog.opcode[t, c] == 1:
                src = int(prog.src_idx[t, c])
                assert solve_cycle[src] < t, (src, t)
