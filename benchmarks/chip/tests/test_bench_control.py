"""The comparison that decides ``correct`` fails its control.

The control is the reference put in the program's place, computed in
bfloat16, the precision below the float32 the configurations state.  At a
size a test run holds (each configuration's generator, n cut to at most
2,400 rows), on three seeds: the program's float32 solve (the ``jax``
backend) reads under the configuration's limit and the control over it.
The chip readings at the cells' own sizes are in PERF.md.
"""

import numpy as np
import pytest

from benchmarks.chip import reference, registry

CONFIGS = [c["name"] for c in registry.benchmark()["configs"]]
SEEDS = (1, 2, 2**31 + 9)


def _small(name):
    from repro.core.csr import from_coo

    cfg = registry.load_json("configs", name)
    params = {k: cfg[k] for k in cfg["generator_params"]}
    params["n"] = min(params["n"], 2400)
    coo = registry.load_code("generators", cfg["generator"]).generate(
        **params)
    n = params["n"]
    return (cfg["limit_max_rel_err"], from_coo(n, *coo, name=name),
            reference.Reference(*reference.csr_arrays(n, *coo)))


@pytest.mark.parametrize("name", CONFIGS)
def test_program_under_limit_control_over_it(name):
    from repro.core import api

    limit, mat, ref = _small(name)
    prog = api.compile(mat)
    for seed in SEEDS:
        b = np.random.default_rng(seed).standard_normal((mat.n, 4)) \
            .astype(np.float32)
        exact = ref.solve(b)
        program = reference.rel_err(api.solve_batch(prog, b), exact)
        control = reference.rel_err(ref.solve_lowp(b, "bfloat16"), exact)
        assert program <= limit < control, (seed, program, control)
