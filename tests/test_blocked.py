"""Row-blocked HBM-resident Pallas placement: parity, planning, composition.

The blocked kernel is forced (``placement="blocked"``) in interpret mode on
streams cut into small blocks (the ``small_row_blocks`` fixture), so the
window machinery — boundary fill/flush/shift DMAs across many blocks — is
exercised on matrices whose ``x[n_pad, B]`` footprint exceeds a
(deliberately tiny) configured VMEM threshold, as on a real TPU it would at
paper-scale n.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import api
from repro.core.csr import random_rhs, serial_solve
from repro.core.matrices import generate
from repro.kernels import common
from repro.kernels.sptrsv import kernel, ops
from repro.kernels.common import tiled_bytes

pytestmark = pytest.mark.usefixtures("small_row_blocks")


def _refs(mat, bmat):
    return np.stack(
        [serial_solve(mat, bmat[:, i]) for i in range(bmat.shape[1])], axis=1
    ).astype(np.float32)


# --------------------------------------------------------------- parity
@pytest.mark.parametrize("name,block", [
    ("band_cz", 1024), ("band_cz", 2048), ("chain_1k", 1024),
    ("band_dw2048", 1024),
])
def test_blocked_matches_oracle(name, block, monkeypatch):
    monkeypatch.setattr(ops, "ROW_BLOCK", block)
    mat = generate(name)
    prog = api.compile(mat)
    plan = ops.plan_window(prog)
    assert plan.feasible and plan.num_blocks > 1  # window machinery exercised
    assert plan.window < mat.n                    # genuinely sub-vector VMEM
    b = random_rhs(mat, 3)
    x = ops.solve(prog, b, interpret=True, placement="blocked")
    np.testing.assert_allclose(
        x, serial_solve(mat, b).astype(np.float32), rtol=1e-5, atol=1e-5
    )


def test_blocked_matches_resident_batched():
    mat = generate("band_cz")
    prog = api.compile(mat)
    rng = np.random.default_rng(0)
    bmat = rng.standard_normal((mat.n, 5))
    xb = ops.solve(prog, bmat, interpret=True,
                   placement="blocked")
    xr = ops.solve(prog, bmat, interpret=True,
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
    mode, plan = ops.resolve_placement(prog, nb, vmem_limit_bytes=limit)
    assert mode == "blocked" and plan.feasible
    rng = np.random.default_rng(1)
    bmat = rng.standard_normal((mat.n, nb))
    x = ops.solve(prog, bmat, interpret=True,
                  vmem_limit_bytes=limit)
    np.testing.assert_allclose(x, _refs(mat, bmat), rtol=1e-5, atol=1e-5)


def test_single_block_sweep(monkeypatch):
    """A block longer than the stream -> one window, flush-only path."""
    monkeypatch.setattr(ops, "ROW_BLOCK", 16 * kernel.SEGMENT_ALIGN)
    mat = generate("band_cz")
    prog = api.compile(mat)
    plan = ops.plan_window(prog)
    assert plan.feasible and plan.num_blocks == 1
    b = random_rhs(mat, 5)
    x = ops.solve(prog, b, interpret=True, placement="blocked")
    np.testing.assert_allclose(
        x, serial_solve(mat, b).astype(np.float32), rtol=1e-5, atol=1e-5
    )


def _bit_identical(prog, batch=3):
    from repro.core import executor

    b = np.random.default_rng(11).standard_normal((prog.n, batch)).astype(
        np.float32)
    x = ops.solve(prog, b, interpret=True, placement="blocked")
    return np.array_equal(x, executor.execute_jax(prog, b))


@pytest.mark.parametrize("name", ["band_cz", "band_dw2048"])
def test_blocked_carries_the_running_sum_across_blocks(name):
    """A row whose chain straddles two blocks: its EDGEs at the end of block
    g and the rest in block g+1, the running sum carried over the boundary
    (and its rows over the window shift)."""
    prog = api.compile(generate(name))
    plan = ops.plan_window(prog)
    _, _, _, fin = ops._blocked_order(prog)
    k = ops._stage_blocked(prog, plan).k
    ends = np.arange(1, plan.num_blocks) * k - 1   # each block's last entry
    assert (~fin[ends]).any()
    assert _bit_identical(prog)


@pytest.mark.parametrize("name", ["band_cz", "hpcg_8"])
def test_blocked_final_loads_rows_filled_from_b(name):
    """Rows past block 0's window enter a window from b at a boundary, and
    their FINALs load b from there, as every FINAL does."""
    prog = api.compile(generate(name))
    plan = ops.plan_window(prog)
    _, _, src, fin = ops._blocked_order(prog)
    assert plan.num_blocks > 2 and (src[fin] >= plan.window).any()
    assert _bit_identical(prog)


@pytest.mark.parametrize("fits", [True, False])
@pytest.mark.parametrize("name", ["ckt_add20", "hpcg_8"])
def test_auto_goes_blocked_only_past_vmem(name, fits, monkeypatch):
    """With no ``vmem_limit_bytes``, ``auto`` keeps every program whose
    resident x fits a core's VMEM (`kernels.common.resident_fits`) on the
    resident kernel, whatever its window; past it, a program with a window
    smaller than its x goes blocked, any other stays resident."""
    monkeypatch.setattr(ops, "resident_fits",
                        lambda n, nb=1, budget=None: fits)
    prog = api.compile(generate(name))
    mode, plan = ops.resolve_placement(prog, 1)
    window = ops.plan_window(prog)
    if fits or not window.feasible or window.state_bytes(1) >= tiled_bytes(
            ops._resident_rows(prog), 1):
        assert (mode, plan) == ("resident", None)
    else:
        assert mode == "blocked" and plan == window
    assert name == "ckt_add20" or mode == ("resident" if fits else "blocked")


@pytest.mark.parametrize("n,mode", [
    (253_951, "resident"), (253_952, "blocked"), (262_000, "blocked"),
])
def test_auto_counts_the_kernels_vmem_headroom(n, mode):
    """``auto`` keeps x resident only where the scoped VMEM the resident
    kernel asks for, x and Mosaic's headroom, fits a core: a banded program
    whose x alone would fit the core's 128 MiB, but not with the headroom,
    goes blocked and is not handed to Mosaic resident.  Its n is stretched
    without compiling anything that large."""
    big = dataclasses.replace(api.compile(generate("band_cz")), n=n)
    x_bytes = common.resident_state_bytes(ops._resident_rows(big), 1)
    assert x_bytes <= common.VMEM_CAPACITY
    fits = kernel.vmem_limit(x_bytes) <= common.VMEM_CAPACITY
    assert fits == (mode == "resident") == common.resident_fits(n)
    solver = ops.build_solver_cols(big, 1, interpret=False)
    assert solver.placement == mode


# --------------------------------------------------------------- planning
@pytest.mark.parametrize("name", ["band_cz", "hpcg_8", "band_dw2048"])
def test_plan_window_bounds_envelope(name):
    """Every block's rows sit inside its planned window, and staging
    addresses them at their ring rows, below the spare row that only EDGEs
    store to.  A block's window and the stride entering after it fit the
    ring without two rows sharing a ring row."""
    prog = api.compile(generate(name))
    plan = ops.plan_window(prog)
    assert plan.feasible and plan.num_blocks > 1
    stream = ops._stage_blocked(prog, plan)
    g, k, w, r = plan.num_blocks, stream.k, plan.window, plan.stride
    ring = kernel.ring_rows(w, r)
    src, dst = stream.instr.reshape(g, kernel.ROW_PLANES, k).transpose(
        1, 0, 2)
    _, _, rows, fin = ops._blocked_order(prog)
    for gi, c in enumerate(stream.counts):
        rows_g, fin_g = rows[gi * k:gi * k + c], fin[gi * k:gi * k + c]
        assert (gi * r <= rows_g).all() and (rows_g < gi * r + w).all()
        assert np.array_equal(src[gi, :c], rows_g % ring)
        assert np.array_equal(dst[gi, :c], np.where(fin_g, src[gi, :c],
                                                    ring))
    assert w + r <= ring and ring == w + 2 * r
    assert r % 8 == 0 and w % r == 0
    assert plan.window >= 2 * plan.stride
    assert plan.n_hbm == (plan.num_blocks - 1) * plan.stride + plan.window
    assert plan.n_hbm >= prog.n


def test_threshold_auto_select():
    """Auto placement: resident under the limit, blocked above it, resident
    again when no feasible window exists (hub-heavy circuit DAG)."""
    prog = api.compile(generate("band_cz"))
    mode, plan = ops.resolve_placement(prog, 8, vmem_limit_bytes=1 << 30)
    assert (mode, plan) == ("resident", None)
    mode, plan = ops.resolve_placement(prog, 8, vmem_limit_bytes=1024)
    assert mode == "blocked" and plan.feasible and plan.window < prog.n

    ckt = api.compile(generate("ckt_rajat04"))
    assert not ops.plan_window(ckt).feasible
    mode, plan = ops.resolve_placement(ckt, 8, vmem_limit_bytes=1024)
    assert mode == "resident"  # infeasible window -> graceful fallback
    with pytest.raises(ValueError, match="infeasible"):
        ops.resolve_placement(ckt, 8, placement="blocked")


def test_x_block_rows_floor():
    prog = api.compile(generate("band_cz"))
    small = ops.plan_window(prog)
    floored = ops.plan_window(prog, min_window=small.window + 64)
    assert floored.window >= small.window + 64
    assert floored.window % 8 == 0


# --------------------------------------------------------------- caching
def test_pallas_executor_cached_per_knobs():
    from repro.core.executor import _EXEC_CACHE, make_pallas_executor

    prog = api.compile(generate("band_cz"))
    make_pallas_executor(prog, batch=5, placement="blocked", interpret=True)
    n_entries = len(_EXEC_CACHE[prog])
    # same padded width + knobs -> cache hit, no new entry
    make_pallas_executor(prog, batch=7, placement="blocked", interpret=True)
    assert len(_EXEC_CACHE[prog]) == n_entries
    # different placement -> its own entry
    make_pallas_executor(prog, batch=5, placement="resident", interpret=True)
    assert len(_EXEC_CACHE[prog]) == n_entries + 1


# ----------------------------------------------------------- composition
def test_api_solve_batch_pallas_blocked():
    mat = generate("band_cz")
    prog = api.compile(mat)
    rng = np.random.default_rng(2)
    bmat = rng.standard_normal((mat.n, 6))
    x = api.solve_batch(prog, bmat, backend="pallas", placement="blocked",
                        interpret=True)
    np.testing.assert_allclose(x, _refs(mat, bmat), rtol=1e-5, atol=1e-5)
    solver = api.make_solver(prog, batch=6, backend="pallas",
                             placement="blocked", interpret=True)
    assert solver.placement == "blocked"
    np.testing.assert_allclose(np.asarray(solver(bmat)), x,
                               rtol=1e-6, atol=1e-6)


def test_solve_split_composes_with_blocked():
    mat = generate("band_dw2048")
    prog, split = api.compile_split(mat, max_indegree=16)
    rng = np.random.default_rng(3)
    bmat = rng.standard_normal((mat.n, 4))
    x = api.solve_split(prog, split, bmat, backend="pallas",
                        placement="blocked", interpret=True)
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
                        placement="blocked", interpret=True)
    np.testing.assert_allclose(x, _refs(mat, bmat), rtol=1e-5, atol=1e-5)
