"""Compile the main solve path for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, and it compiles for a device that is
described (`jax.experimental.topologies`) rather than attached.  These
tests therefore catch what interpret mode cannot: primitives Mosaic does
not lower, misaligned slices, and more VMEM/SMEM than a kernel may use.
Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  All described-chip compiles of the repo live in this one file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import api, compiler, executor, shard
from repro.core.matrices import generate
from repro.kernels import common
from repro.kernels.sptrsv import kernel, ops

B = 16
CPB = 128
V5E_VMEM = 128 << 20  # physical VMEM of one v5e TensorCore
V5E_HBM = 16 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Described-chip compiles cannot be read back from the persistent cache
    without a chip; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def progs():
    return {name: api.compile(generate(name))
            for name in ("ckt_add20", "band_huge64k")}


def _kernel_args(prog, rows, one_chip, batch=B, blocked=False):
    """Argument shapes of the resident kernel (its row-serial stream) or,
    with ``blocked``, of the blocked kernel (its lane-state stream)."""
    if blocked:
        stream = ops._stage_instructions(prog, CPB)
        kw = dict(num_cus=prog.num_cus, num_slots=executor._psum_slots(prog),
                  interpret=False)
    else:
        stream = ops._stage_rows(prog, CPB)
        kw = dict(interpret=False)

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    return (spec(stream.instr), spec(stream.values), spec(stream.counts),
            spec(np.zeros((rows, batch), np.float32))), kw


def test_resident_kernel_compiles_for_v5e(progs, one_chip, no_compile_cache):
    prog = progs["ckt_add20"]
    mode, _ = ops.resolve_placement(prog, B)
    assert mode == "resident"
    args, kw = _kernel_args(prog, ops._resident_rows(prog), one_chip)
    compiled = kernel.sptrsv_pallas.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    acct = ops.state_bytes(prog, B, placement="resident")
    assert acct["vmem"] <= kernel.vmem_limit(acct["vmem"]) <= V5E_VMEM


def test_resident_kernel_compiles_at_hpcg_rows(progs, one_chip,
                                               no_compile_cache):
    """The resident kernel at the benchmark's HPCG grid (48³ = 110,592 rows
    and the spare row, padded to 110,600) and one column: one VMEM x buffer,
    b copied into it.  The stream is ckt_add20's; the VMEM the kernel holds
    depends only on n_pad and B."""
    prog = progs["ckt_add20"]
    rows = 48 ** 3 + 8
    args, kw = _kernel_args(prog, rows, one_chip, batch=1)
    compiled = kernel.sptrsv_pallas.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    state = common.resident_state_bytes(rows, 1)
    assert state == common.tiled_bytes(rows, 1)  # no lane state
    assert state <= 60 * 10 ** 6
    assert state < kernel.vmem_limit(state) <= V5E_VMEM


def test_blocked_kernel_compiles_for_v5e(progs, one_chip, no_compile_cache):
    prog = progs["band_huge64k"]
    mode, plan = ops.resolve_placement(prog, B)
    assert mode == "blocked"  # auto: x+b of 64k rows is past the threshold
    args, kw = _kernel_args(prog, plan.n_hbm, one_chip, blocked=True)
    compiled = kernel.sptrsv_pallas_blocked.lower(
        *args, window=plan.window, stride=plan.stride, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the compiler refuses a kernel past its scoped-VMEM limit, so the
    # compile passing proves the accounted window state fits under it
    acct = ops.state_bytes(prog, B, placement="blocked", plan=plan)
    limit = kernel.vmem_limit(acct["vmem"])
    assert acct["vmem"] < limit <= V5E_VMEM
    assert acct["instr"] <= 1 << 20  # the SMEM stream buffers fit SMEM
    mem = compiled.memory_analysis()
    hbm = (mem.argument_size_in_bytes + mem.output_size_in_bytes
           + mem.temp_size_in_bytes)
    assert 0 < hbm < V5E_HBM


def test_blocked_kernel_compiles_at_lap7_rows(one_chip, no_compile_cache):
    """The blocked kernel at the geometry `plan_window` gives lap7_ic0_72 in
    level order (373,248 rows: window 21,832 at stride 1,032 over 346
    cycle blocks), one column, and the largest segment a 128-cycle block
    can have (every lane active).  Shapes alone: nothing that large is
    compiled by the program here."""
    blocks, k, window, stride = 346, CPB * 64, 21832, 1032
    rows = (blocks - 1) * stride + window

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((blocks * kernel.STREAM_PLANES * k,), jnp.int32),
            spec((blocks * k,), jnp.float32), spec((blocks,), jnp.int32),
            spec((rows, 1), jnp.float32))
    compiled = kernel.sptrsv_pallas_blocked.lower(
        *args, num_cus=64, num_slots=12, window=window, stride=stride,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    state = kernel.blocked_state_bytes(window, 128, 64, 12)
    assert state < kernel.vmem_limit(state) <= V5E_VMEM
    assert 2 * k * (4 * kernel.STREAM_PLANES + 4) <= 1 << 20  # SMEM


def test_level_ordered_solve_compiles_for_v5e(one_chip, no_compile_cache,
                                              monkeypatch):
    """A level-ordered program's whole jitted solve: b gathered into the
    program's row order, the blocked kernel, x gathered back.  The level
    order is forced at this small size."""
    monkeypatch.setattr(compiler.order, "wants_level", lambda n: True)
    prog = api.compile(generate("lap7_ic0_8"))
    assert prog.row_perm is not None
    solve = ops.build_solver_cols(prog, 1, cycles_per_block=32,
                                  placement="blocked", interpret=False)
    b = jax.ShapeDtypeStruct((prog.n, 1), jnp.float32, sharding=one_chip)
    text = solve.lower(b).compile().as_text()
    assert "tpu_custom_call" in text and "gather" in text


def test_scan_executor_compiles_for_v5e(progs, one_chip, no_compile_cache):
    prog = progs["ckt_add20"]
    solve_cols = executor.build_solve_cols(prog, B)
    b = jax.ShapeDtypeStruct((prog.n, B), jnp.float32, sharding=one_chip)
    compiled = jax.jit(solve_cols).lower(b).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= prog.n * B * 4


# the cases of `chip_smoke.py --four-chips`
@pytest.mark.parametrize("name,backend", [
    ("ckt_add20", "jax"), ("ckt_add20", "pallas"), ("band_huge64k", "pallas"),
])
def test_sharded_solve_compiles_for_four_chips(progs, topo, name, backend,
                                               no_compile_cache):
    """The mesh path: RHS columns over the four chips of a v5e 2x2 host."""
    prog = progs[name]
    mesh = Mesh(np.asarray(topo.devices), ("batch",))
    assert mesh.size == 4
    opts = {"interpret": False} if backend == "pallas" else {}
    w_local, width = shard.sharded_widths(4 * B, mesh)
    fn = shard._build_sharded_executor(prog, w_local, mesh, backend, opts)
    b = jax.ShapeDtypeStruct(
        (prog.n, width), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec(None, "batch")))
    text = fn.lower(b).compile().as_text()
    assert "all-gather" not in text and "all-reduce" not in text
    if backend == "pallas":
        assert "tpu_custom_call" in text
