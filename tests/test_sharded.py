"""Multi-device batched execution (`repro.core.shard`).

Fast tests run in-process on whatever devices exist (a 1-device mesh still
exercises the full shard_map/placement/cache path).  The genuinely
multi-device checks force a 4- and an 8-device CPU host via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in a subprocess —
the flag must be set before jax initializes.  At ~6 s each the subprocess
tests stay inside the fast ``-m "not slow"`` loop.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import api, executor, shard
from repro.core.matrices import generate


@pytest.fixture(scope="module")
def prog():
    return api.compile(generate("band_cz"))


@pytest.fixture(scope="module")
def mesh():
    return shard.batch_mesh()


# uneven (not divisible by any device count), even, and B=1 degenerate
@pytest.mark.parametrize("B", [1, 5, 8])
def test_sharded_matches_numpy_oracle(prog, mesh, B):
    bmat = np.random.default_rng(B).standard_normal((prog.n, B))
    got = api.solve_batch(prog, bmat, mesh=mesh)
    ref = api.solve_numpy(prog, bmat)
    assert got.shape == (prog.n, B)
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-12)
    assert rel <= 1e-5, (B, rel)


def test_sharded_cache_no_retrace(prog, mesh):
    rng = np.random.default_rng(2)
    # any B <= ndev pads to one column per device: same per-device width,
    # so all of these must share a single trace (valid for any mesh size)
    ndev = mesh.size
    sizes = sorted({1, max(1, ndev - 1), ndev})
    assert len({shard.sharded_widths(b, mesh) for b in sizes}) == 1
    api.solve_batch(prog, rng.standard_normal((prog.n, ndev)), mesh=mesh)  # prime
    before = executor.trace_count()
    for b in sizes:
        api.solve_batch(prog, rng.standard_normal((prog.n, b)), mesh=mesh)
    assert executor.trace_count() == before


def test_make_solver_mesh_shares_cache(prog, mesh):
    rng = np.random.default_rng(3)
    b = rng.standard_normal((prog.n, 4))
    x1 = np.asarray(api.make_solver(prog, batch=4, mesh=mesh)(b))
    before = executor.trace_count()
    x2 = np.asarray(api.make_solver(prog, batch=4, mesh=mesh)(b))
    assert executor.trace_count() == before
    np.testing.assert_allclose(x1, x2)
    with pytest.raises(ValueError):
        api.make_solver(prog, mesh=mesh)  # mesh requires explicit batch


def test_uneven_padding_roundtrip(prog, mesh):
    """B not divisible by the device count: pad columns must not leak."""
    ndev = mesh.size
    B = 7 if ndev != 7 else 9
    assert B % ndev != 0 or ndev == 1
    bmat = np.random.default_rng(4).standard_normal((prog.n, B))
    got = api.solve_batch(prog, bmat, mesh=mesh)
    assert got.shape == (prog.n, B)
    ref = api.solve_numpy(prog, bmat)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    # solving a subset of the same columns agrees column-for-column
    sub = api.solve_batch(prog, bmat[:, :3], mesh=mesh)
    np.testing.assert_allclose(sub, got[:, :3], rtol=1e-5, atol=1e-6)


def test_sharded_widths():
    mesh = shard.batch_mesh(num_devices=1)
    assert shard.sharded_widths(1, mesh) == (1, 1)
    assert shard.sharded_widths(3, mesh) == (8, 8)


def test_split_composes_with_sharded_path(mesh):
    """Node splitting + sharded batch: the full composition of this PR."""
    mat = generate("hub_small")
    prog, split = api.compile_split(mat, max_indegree=48)
    bmat = np.random.default_rng(5).standard_normal((mat.n, 6))
    got = api.solve_split(prog, split, bmat, mesh=mesh)
    ref = np.stack(
        [api.reference_solve(mat, bmat[:, i]) for i in range(6)], axis=1
    )
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# forced multi-device host (subprocess: XLA_FLAGS must precede jax init)

SCRIPT = r"""
import os, sys
ndev = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
import json
import numpy as np
import jax
from repro.core import api, executor, shard
from repro.core.csr import serial_solve
from repro.core.matrices import generate

out = {"devices": len(jax.devices()), "cases": []}
mat = generate("band_cz")
prog = api.compile(mat)
mesh = shard.batch_mesh()
rng = np.random.default_rng(1)
for B in [1, 7, 8, 32]:
    bmat = rng.standard_normal((mat.n, B))
    before = executor.trace_count()
    got = api.solve_batch(prog, bmat, mesh=mesh)
    ref = np.stack([serial_solve(mat, bmat[:, i]) for i in range(B)], axis=1)
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    w_local, _ = shard.sharded_widths(B, mesh)
    out["cases"].append({"B": B, "w_local": w_local, "rel": rel,
                         "traces": executor.trace_count() - before})
# where the columns really live: one column block per device
B = 8 * ndev
bmat = rng.standard_normal((mat.n, B))
ref = np.stack([serial_solve(mat, bmat[:, i]) for i in range(B)], axis=1)
for backend, opts in (("jax", {}), ("pallas", {"interpret": True})):
    x = shard.make_sharded_solver(prog, B, mesh, backend=backend, **opts)(bmat)
    out[backend] = {
        "shard_devices": len({s.device for s in x.addressable_shards}),
        "shard_cols": sorted({s.data.shape[1] for s in x.addressable_shards}),
        "rel": float(np.abs(np.asarray(x) - ref).max() / np.abs(ref).max()),
    }
print(json.dumps(out))
"""


@pytest.mark.parametrize("ndev", [4, 8])
def test_forced_device_mesh(ndev):
    # ~6 s (subprocess jax init + a few traces): stays inside the fast loop
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(ndev)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == ndev
    for backend in ("jax", "pallas"):
        case = out[backend]
        assert case["shard_devices"] == ndev, case  # not all on device 0
        assert case["shard_cols"] == [8], case
        assert case["rel"] <= 1e-5, case
    seen_widths = set()
    for case in out["cases"]:
        assert case["rel"] <= 1e-5, case
        # at most one trace per (program, per-device width, mesh): a repeat
        # of an already-seen width must not trace at all
        expected = 0 if case["w_local"] in seen_widths else 1
        assert case["traces"] <= expected, (case, seen_widths)
        seen_widths.add(case["w_local"])
