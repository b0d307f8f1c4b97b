"""The closed-loop traffic driver at tiny size on the CPU, against the
program's ``jax`` backend."""

import numpy as np

from benchmarks.chip import reference, registry
from benchmarks.chip.generators import circuit


def _matrix(n=300):
    from repro.core.csr import from_coo

    coo = circuit.generate(n, 6, 3.1, 5)
    return (from_coo(n, *coo, name="tiny"),
            reference.Reference(*reference.csr_arrays(n, *coo)))


def test_closed_batch_counts_calls_and_keeps_a_sample():
    from repro.core import api

    mat, ref = _matrix()
    prog = api.compile(mat)
    rng = np.random.default_rng(0)
    pool = [rng.standard_normal((mat.n, 4)).astype(np.float32)
            for _ in range(3)]
    drv = registry.load_code("drivers", "closed_batch")
    solve = lambda b: api.solve_batch(prog, b)  # noqa: E731
    solve(pool[0])
    res = drv.run(solve, pool, 0.2, seed=7, keep=5)
    assert res["failed"] == 0 and res["attempted"] >= 5
    assert res["columns"] == 4 * res["attempted"]
    assert res["window_s"] >= 0.2
    assert len(res["kept"]) == 5
    for k, x in res["kept"]:
        assert reference.rel_err(x, ref.solve(pool[k])) < 1e-5


def test_closed_batch_counts_failures():
    drv = registry.load_code("drivers", "closed_batch")

    def broken(b):
        raise RuntimeError("device lost")

    res = drv.run(broken, [np.zeros((3, 1))], 0.01, seed=0)
    assert res["failed"] == res["attempted"] >= 1 and not res["kept"]
