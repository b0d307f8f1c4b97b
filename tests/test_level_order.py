"""The row order the compiler owns (`compiler/order.py`, DESIGN.md §1).

The ``level_order`` pass relabels rows by DAG level only when one column
of x cannot stay in a core's VMEM, so every suite matrix keeps its order
and compiles as it did before the pass existed.  The tests force the level
order at small sizes (`level_compile`); every executor still takes b and
returns x in the matrix's order.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import api, compiler, serialize, shard
from repro.core.compiler import order
from repro.core.csr import serial_solve, serial_solve_upper, transpose_upper
from repro.core.errors import PlacementInfeasibleError
from repro.core.frontends.sptrsv import lower_tri
from repro.core.frontends.upper import lower_upper
from repro.core.matrices import SUITE, generate, laplace7_ic0, \
    laplace7_ic0_coo
from repro.kernels import common
from repro.kernels.sptrsv import ops

MATS = ("lap7_ic0_8", "hpcg_8", "ckt_rajat04")


def level_compile(compile_fn, *args):
    """``compile_fn(*args)`` with the level order forced: the pass picks it
    on its own only past a core's VMEM."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(order, "wants_level", lambda n: True)
        return compile_fn(*args)


@pytest.fixture(scope="module")
def level_progs():
    return {name: level_compile(api.compile, generate(name))
            for name in MATS}


def _serial(mat, bmat):
    return np.stack([serial_solve(mat, bmat[:, i])
                     for i in range(bmat.shape[1])], axis=1)


# ------------------------------------------------------------ the order
@pytest.mark.parametrize("name", MATS)
def test_level_order_is_topological_with_contiguous_levels(name,
                                                           level_progs):
    prog = level_progs[name]
    dag = lower_tri(generate(name))
    perm = prog.row_perm
    assert perm is not None
    assert np.array_equal(np.sort(perm), np.arange(dag.n))
    level = order.levels(dag)
    # levels are contiguous row ranges: non-decreasing along the new order
    assert np.all(np.diff(level[perm]) >= 0)
    # ties keep the natural order
    same = np.diff(level[perm]) == 0
    assert np.all(np.diff(perm)[same] > 0)
    # every source of an internal row is an earlier internal row
    new, edge = order.permute(dag, perm)
    rows = np.repeat(np.arange(dag.n), np.diff(new.ptr))
    assert np.all(new.src < rows)
    new.validate()
    inv = np.empty_like(perm)
    inv[perm] = np.arange(dag.n)
    assert np.array_equal(inv[dag.src[edge]], new.src)
    np.testing.assert_array_equal(new.weight, dag.weight[edge])
    by = {p.name: p for p in prog.stats.pass_stats}
    assert by["level_order"].metrics == {
        "levels": int(level.max()) + 1,
        "widest_level": int(np.bincount(level).max()), "reordered": True}


def test_level_order_compiles_the_permuted_matrix():
    """The level order compiles P L Pᵀ: the same words as compiling the
    symmetrically permuted matrix in its own order."""
    from repro.core.csr import from_coo

    mat = generate("lap7_ic0_8")
    prog = level_compile(api.compile, mat)
    perm = prog.row_perm
    inv = np.empty_like(perm)
    inv[perm] = np.arange(mat.n)
    dag = lower_tri(mat)
    rows = np.repeat(np.arange(mat.n), np.diff(dag.ptr))
    permuted = from_coo(mat.n, inv[rows], inv[dag.src], dag.weight,
                        mat.diag()[perm], name=mat.name)
    ref = api.compile(permuted)
    assert ref.row_perm is None
    np.testing.assert_array_equal(prog.instr, ref.instr)
    np.testing.assert_array_equal(prog.stream, ref.stream)


# ------------------------------------------------------------ executors
@pytest.mark.parametrize("batch", [1, 16])
@pytest.mark.parametrize("name", MATS)
def test_every_executor_solves_in_the_users_order(name, batch, level_progs):
    mat, prog = generate(name), level_progs[name]
    bmat = np.random.default_rng(batch).standard_normal((mat.n, batch))
    ref = _serial(mat, bmat)
    got = {
        "numpy": api.solve_numpy(prog, bmat),
        "jax": api.solve_batch(prog, bmat),
        "resident": api.solve_batch(prog, bmat, backend="pallas",
                                    placement="resident", interpret=True),
    }
    blocked = dict(backend="pallas", placement="blocked", interpret=True,
                   cycles_per_block=32)
    if ops.plan_window(prog, 32).feasible:
        solver = api.make_solver(prog, batch=batch, **blocked)
        assert solver.placement == "blocked" and solver.plan.num_blocks > 1
        got["blocked"] = np.asarray(solver(bmat))
    else:
        # hub columns keep early rows live in either order
        assert name == "ckt_rajat04"
        with pytest.raises(PlacementInfeasibleError):
            api.make_solver(prog, batch=batch, **blocked)
    for backend, x in got.items():
        np.testing.assert_allclose(x, ref, rtol=2e-4, atol=1e-4,
                                   err_msg=backend)
    # the one-column forms
    np.testing.assert_allclose(api.solve(prog, bmat[:, 0]), ref[:, 0],
                               rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(api.solve_numpy(prog, bmat[:, 0]), ref[:, 0],
                               rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("backend,opts", [("jax", {}),
                                          ("pallas", {"interpret": True})])
def test_sharded_path_solves_in_the_users_order(backend, opts, level_progs):
    mat, prog = generate("lap7_ic0_8"), level_progs["lap7_ic0_8"]
    bmat = np.random.default_rng(7).standard_normal((mat.n, 5))
    got = api.solve_batch(prog, bmat, mesh=shard.batch_mesh(),
                          backend=backend, **opts)
    np.testing.assert_allclose(got, _serial(mat, bmat), rtol=2e-4,
                               atol=1e-4)


def test_frontend_perm_composes_with_row_order():
    """The upper-triangular frontend's own permutation around a program
    that the compiler relabelled again."""
    mat = generate("hpcg_8")
    upper = transpose_upper(mat)
    dag, perm = lower_upper(upper)
    cw = api.CompiledWorkload(level_compile(compiler.compile_dag, dag),
                              perm=perm)
    assert cw.program.row_perm is not None
    b = np.random.default_rng(3).standard_normal(mat.n)
    np.testing.assert_allclose(cw.solve(b), serial_solve_upper(upper, b),
                               rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(cw.solve(b, backend="numpy"),
                               serial_solve_upper(upper, b), rtol=2e-4,
                               atol=1e-4)


def test_identity_order_traces_no_gather():
    """A program in the matrix's order gets the solve it had before the
    pass: no gather in its traced program."""
    import jax

    from repro.core import executor

    def outer_ops(prog):
        b = jax.ShapeDtypeStruct((prog.n, 8), np.float32)
        jaxpr = jax.make_jaxpr(executor.build_solve_cols(prog, 8))(b)
        return [e.primitive.name for e in jaxpr.jaxpr.eqns]

    prog = api.compile(generate("hpcg_8"))
    assert prog.row_perm is None
    assert executor.permute_rows(prog, outer_ops) is outer_ops
    assert "gather" not in outer_ops(prog)
    level = outer_ops(level_compile(api.compile, generate("hpcg_8")))
    assert level.count("gather") == 2  # b in, x out


# ------------------------------------------------------------ the mechanism
def test_level_order_makes_the_window_slide():
    """At 32³ the natural order keeps over 80% of the rows in the window;
    level order under 25% (27,408 and 6,192 of 32,768)."""
    mat = laplace7_ic0(32, "lap7_ic0_32")
    natural = ops.plan_window(api.compile(mat))
    level = ops.plan_window(level_compile(api.compile, mat))
    assert natural.feasible and level.feasible
    assert natural.window > 0.8 * mat.n
    assert level.window < 0.25 * mat.n
    assert (natural.window, level.window) == (27408, 6192)


# ------------------------------------------------------------ auto
def test_auto_keeps_every_suite_matrix_natural():
    for name in SUITE:
        if name == "lap7_ic0_72":
            continue  # the one matrix past VMEM: next test
        assert not order.wants_level(generate(name).n), name
    for name in ("ckt_rajat04", "hpcg_8", "band_cz"):
        prog = api.compile(generate(name))
        assert prog.row_perm is None
        assert prog.stats.pass_stats[0].name == "level_order"
        assert prog.stats.pass_stats[0].metrics == {"reordered": False}


def test_auto_picks_level_past_vmem_without_compiling():
    assert order.wants_level(72 ** 3)          # lap7_ic0_72: 373,248 rows
    assert order.wants_level(64 ** 3)          # over by one 4 KB tile row
    assert not order.wants_level(64 ** 3 - 1)  # exactly 128 MiB
    assert common.tiled_bytes(64 ** 3, 1) == common.VMEM_CAPACITY


# ------------------------------------------------------------ life cycle
def test_row_perm_survives_serialization(level_progs, tmp_path):
    prog = level_progs["hpcg_8"]
    path = tmp_path / "hpcg_8.prog"
    api.save_program(prog, path)
    back = api.load_program(path)
    np.testing.assert_array_equal(back.row_perm, prog.row_perm)
    b = np.random.default_rng(4).standard_normal((prog.n, 2))
    np.testing.assert_array_equal(api.solve_batch(back, b),
                                  api.solve_batch(prog, b))
    # a permutation that is not one is refused at load
    bad = dataclasses.replace(prog, row_perm=np.zeros(prog.n, np.int32))
    with pytest.raises(Exception, match="row_perm"):
        serialize.loads_program(serialize.dumps_program(bad))


def test_recompile_values_in_level_order_equals_a_full_recompile():
    mat = generate("lap7_ic0_8")
    prog = level_compile(api.compile, mat)
    scaled = dataclasses.replace(mat, values=mat.values * np.linspace(
        0.5, 1.5, mat.nnz))
    fast = api.recompile_values(prog, scaled)
    full = level_compile(api.compile, scaled)
    np.testing.assert_array_equal(fast.stream, full.stream)
    np.testing.assert_array_equal(fast.row_perm, full.row_perm)
    b = np.random.default_rng(5).standard_normal((mat.n, 3))
    np.testing.assert_allclose(api.solve_batch(fast, b), _serial(scaled, b),
                               rtol=2e-4, atol=1e-4)


# ------------------------------------------------------------ refusal
@pytest.mark.parametrize("placement", ["auto", "resident"])
def test_placement_past_vmem_is_refused(placement):
    """A compiled resident solve whose x is past a core's VMEM, with no
    smaller window, raises before Mosaic is asked (the hub DAG has no
    window; its n is stretched past 2^18 rows without compiling anything
    that large).  The interpreter has no VMEM and builds it."""
    prog = api.compile(generate("ckt_rajat04"))
    big = dataclasses.replace(prog, n=300_000)
    with pytest.raises(PlacementInfeasibleError, match="300008 rows") as e:
        ops.build_solver_cols(big, 1, placement=placement, interpret=False)
    assert e.value.detail["vmem_bytes"] == common.tiled_bytes(300_008, 1)
    assert e.value.detail["vmem_bytes"] > common.VMEM_CAPACITY
    assert ops.build_solver_cols(big, 1, placement=placement,
                                 interpret=True).placement == "resident"
    # under the capacity the same program builds a compiled resident solve
    assert ops.build_solver_cols(prog, 1, placement=placement,
                                 interpret=False).placement == "resident"


# ------------------------------------------------------------ the generator
def _dense_ic0(a):
    """Plain float64 incomplete Cholesky with no fill on A's pattern."""
    n = a.shape[0]
    pattern = np.tril(a != 0)
    low = np.tril(a).astype(np.float64)
    for k in range(n):
        low[k, k] = np.sqrt(low[k, k])
        col = pattern[k + 1:, k]
        low[k + 1:, k][col] /= low[k, k]
        for j in range(k + 1, n):
            if low[j, k] == 0.0:
                continue
            rows = np.flatnonzero(pattern[j:, j]) + j
            low[rows, j] -= low[rows, k] * low[j, k]
    return np.where(pattern, low, 0.0)


def _laplacian(nx):
    n = nx ** 3
    a = 6.0 * np.eye(n)
    for i in range(n):
        ix, iy, iz = i % nx, (i // nx) % nx, i // (nx * nx)
        for has, off in ((ix > 0, 1), (iy > 0, nx), (iz > 0, nx * nx)):
            if has:
                a[i, i - off] = a[i - off, i] = -1.0
    return a


def _factor(nx):
    rows, cols, vals, diag = laplace7_ic0_coo(nx)
    low = np.diag(diag)
    low[rows, cols] = vals
    return low


@pytest.mark.parametrize("nx", [3, 4, 5])
def test_closed_form_is_the_incomplete_cholesky_factor(nx):
    np.testing.assert_allclose(_factor(nx), _dense_ic0(_laplacian(nx)),
                               rtol=1e-13, atol=1e-15)


@pytest.mark.parametrize("nx", [3, 4, 5, 8])
def test_factor_reproduces_a_on_its_pattern(nx):
    a = _laplacian(nx)
    low = _factor(nx)
    prod = low @ low.T
    np.testing.assert_allclose(prod[a != 0], a[a != 0], rtol=0, atol=1e-13)
    assert np.all(np.tril(low) == low)
