"""Row-blocked HBM-resident Pallas placement: parity, planning, composition.

The blocked kernel is forced (``placement="blocked"``) with a small
``cycles_per_block`` in interpret mode, so the window machinery — boundary
flush/shift DMAs across many cycle blocks — is exercised on matrices
whose ``x[n_pad, B]`` footprint exceeds a (deliberately tiny) configured
VMEM threshold, as on a real TPU it would at paper-scale n.
"""

import numpy as np
import pytest

from repro.core import api
from repro.core.csr import random_rhs, serial_solve
from repro.core.matrices import generate
from repro.kernels.sptrsv import ops
from repro.kernels.common import tiled_bytes


def _refs(mat, bmat):
    return np.stack(
        [serial_solve(mat, bmat[:, i]) for i in range(bmat.shape[1])], axis=1
    ).astype(np.float32)


# --------------------------------------------------------------- parity
@pytest.mark.parametrize("name,cpb", [
    ("band_cz", 64), ("band_cz", 32), ("chain_1k", 128), ("band_dw2048", 64),
])
def test_blocked_matches_oracle(name, cpb):
    mat = generate(name)
    prog = api.compile(mat)
    plan = ops.plan_window(prog, cpb)
    assert plan.feasible and plan.num_blocks > 1  # window machinery exercised
    assert plan.window < mat.n                    # genuinely sub-vector VMEM
    b = random_rhs(mat, 3)
    x = ops.solve(prog, b, cycles_per_block=cpb, interpret=True,
                  placement="blocked")
    np.testing.assert_allclose(
        x, serial_solve(mat, b).astype(np.float32), rtol=1e-5, atol=1e-5
    )


def test_blocked_matches_resident_batched():
    mat = generate("band_cz")
    prog = api.compile(mat)
    rng = np.random.default_rng(0)
    bmat = rng.standard_normal((mat.n, 5))
    xb = ops.solve(prog, bmat, cycles_per_block=64, interpret=True,
                   placement="blocked")
    xr = ops.solve(prog, bmat, cycles_per_block=64, interpret=True,
                   placement="resident")
    np.testing.assert_allclose(xb, xr, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(xb, _refs(mat, bmat), rtol=1e-5, atol=1e-5)


def test_blocked_past_vmem_threshold():
    """Acceptance: x[n_pad, B] footprint above the configured threshold ->
    auto placement goes blocked, and the solve still matches the oracle."""
    mat = generate("band_cz")
    prog = api.compile(mat)
    nb = 8
    # just below the resident footprint: one tiled x buffer, b copied in
    limit = tiled_bytes(ops._resident_rows(prog), nb) - 1
    mode, plan = ops.resolve_placement(prog, nb, vmem_limit_bytes=limit,
                                       cycles_per_block=64)
    assert mode == "blocked" and plan.feasible
    rng = np.random.default_rng(1)
    bmat = rng.standard_normal((mat.n, nb))
    x = ops.solve(prog, bmat, cycles_per_block=64, interpret=True,
                  vmem_limit_bytes=limit)
    np.testing.assert_allclose(x, _refs(mat, bmat), rtol=1e-5, atol=1e-5)


def test_single_block_sweep():
    """cycles_per_block > program cycles -> one window, flush-only path."""
    mat = generate("band_cz")
    prog = api.compile(mat)
    plan = ops.plan_window(prog, 1024)
    assert plan.feasible and plan.num_blocks == 1
    b = random_rhs(mat, 5)
    x = ops.solve(prog, b, cycles_per_block=1024, interpret=True,
                  placement="blocked")
    np.testing.assert_allclose(
        x, serial_solve(mat, b).astype(np.float32), rtol=1e-5, atol=1e-5
    )


# --------------------------------------------------------------- planning
def test_plan_window_bounds_envelope():
    """Every cycle block's touched rows must sit inside its planned window."""
    prog = api.compile(generate("band_cz"))
    cpb = 64
    plan = ops.plan_window(prog, cpb)
    assert plan.feasible
    t = prog.cycles
    g = -(-t // cpb)
    for gi in range(g):
        sl = slice(gi * cpb, min((gi + 1) * cpb, t))
        hi = prog.row_hi[sl].max()
        if hi < 0:
            continue
        lo = prog.row_lo[sl][prog.row_hi[sl] >= 0].min()
        assert gi * plan.stride <= lo
        assert hi < gi * plan.stride + plan.window
    assert plan.window >= 2 * plan.stride
    assert plan.n_hbm == (plan.num_blocks - 1) * plan.stride + plan.window


def test_row_metadata_emitted():
    prog = api.compile(generate("chain_1k"))
    assert prog.row_lo is not None and prog.row_hi is not None
    assert prog.row_lo.shape == (prog.cycles,)
    active = prog.row_hi >= 0
    assert (prog.row_lo[active] <= prog.row_hi[active]).all()
    assert prog.row_hi.max() == prog.n - 1  # last row is touched somewhere


def test_threshold_auto_select():
    """Auto placement: resident under the limit, blocked above it, resident
    again when no feasible window exists (hub-heavy circuit DAG)."""
    prog = api.compile(generate("band_cz"))
    mode, plan = ops.resolve_placement(prog, 8, vmem_limit_bytes=1 << 30)
    assert (mode, plan) == ("resident", None)
    mode, plan = ops.resolve_placement(prog, 8, vmem_limit_bytes=1024,
                                       cycles_per_block=64)
    assert mode == "blocked" and plan.feasible and plan.window < prog.n

    ckt = api.compile(generate("ckt_rajat04"))
    assert not ops.plan_window(ckt, 128).feasible
    mode, plan = ops.resolve_placement(ckt, 8, vmem_limit_bytes=1024)
    assert mode == "resident"  # infeasible window -> graceful fallback
    with pytest.raises(ValueError, match="infeasible"):
        ops.resolve_placement(ckt, 8, placement="blocked")


def test_x_block_rows_floor():
    prog = api.compile(generate("band_cz"))
    small = ops.plan_window(prog, 64)
    floored = ops.plan_window(prog, 64, min_window=small.window + 64)
    assert floored.window >= small.window + 64
    assert floored.window % 8 == 0


# --------------------------------------------------------------- caching
def test_pallas_executor_cached_per_knobs():
    from repro.core.executor import _EXEC_CACHE, make_pallas_executor

    prog = api.compile(generate("band_cz"))
    make_pallas_executor(prog, batch=5, cycles_per_block=64,
                         placement="blocked", interpret=True)
    n_entries = len(_EXEC_CACHE[prog])
    # same padded width + knobs -> cache hit, no new entry
    make_pallas_executor(prog, batch=7, cycles_per_block=64,
                         placement="blocked", interpret=True)
    assert len(_EXEC_CACHE[prog]) == n_entries
    # different placement -> its own entry
    make_pallas_executor(prog, batch=5, cycles_per_block=64,
                         placement="resident", interpret=True)
    assert len(_EXEC_CACHE[prog]) == n_entries + 1


# ----------------------------------------------------------- composition
def test_api_solve_batch_pallas_blocked():
    mat = generate("band_cz")
    prog = api.compile(mat)
    rng = np.random.default_rng(2)
    bmat = rng.standard_normal((mat.n, 6))
    x = api.solve_batch(prog, bmat, backend="pallas", placement="blocked",
                        cycles_per_block=64, interpret=True)
    np.testing.assert_allclose(x, _refs(mat, bmat), rtol=1e-5, atol=1e-5)
    solver = api.make_solver(prog, batch=6, backend="pallas",
                             placement="blocked", cycles_per_block=64,
                             interpret=True)
    assert solver.placement == "blocked"
    np.testing.assert_allclose(np.asarray(solver(bmat)), x,
                               rtol=1e-6, atol=1e-6)


def test_solve_split_composes_with_blocked():
    mat = generate("band_dw2048")
    prog, split = api.compile_split(mat, max_indegree=16)
    rng = np.random.default_rng(3)
    bmat = rng.standard_normal((mat.n, 4))
    x = api.solve_split(prog, split, bmat, backend="pallas",
                        placement="blocked", cycles_per_block=64,
                        interpret=True)
    np.testing.assert_allclose(x, _refs(mat, bmat), rtol=1e-5, atol=1e-5)


def test_mesh_shards_blocked_pallas():
    """Row-blocked pallas under shard_map: columns over devices, window
    machinery per device.  Single-device mesh on a plain CPU host; the
    forced-8-device variant lives in the slow sharded suite."""
    from repro.core import shard

    mat = generate("band_cz")
    prog = api.compile(mat)
    mesh = shard.batch_mesh()
    rng = np.random.default_rng(4)
    bmat = rng.standard_normal((mat.n, 2 * mesh.size))
    x = api.solve_batch(prog, bmat, mesh=mesh, backend="pallas",
                        placement="blocked", cycles_per_block=64,
                        interpret=True)
    np.testing.assert_allclose(x, _refs(mat, bmat), rtol=1e-5, atol=1e-5)
