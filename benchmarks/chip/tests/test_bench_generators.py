"""The benchmark's generator copies give the matrices their configuration
files pin, and the same arrays as the program's suite entries."""

import numpy as np
import pytest

from benchmarks.chip import reference, registry

CONFIGS = [c["name"] for c in registry.benchmark()["configs"]]


def _generate(cfg):
    gen = registry.load_code("generators", cfg["generator"])
    return gen.generate(**{k: cfg[k] for k in cfg["generator_params"]})


@pytest.mark.parametrize("name", CONFIGS)
def test_fingerprint_pinned(name):
    cfg = registry.load_json("configs", name)
    arrays = reference.csr_arrays(cfg["n"], *_generate(cfg))
    assert reference.fingerprint(*arrays) == cfg["fingerprint"]


@pytest.mark.parametrize("name", CONFIGS)
def test_copy_matches_program_suite(name):
    from repro.core.csr import from_coo
    from repro.core.matrices import generate

    cfg = registry.load_json("configs", name)
    coo = _generate(cfg)
    ours = reference.csr_arrays(cfg["n"], *coo)
    suite = generate(name)
    built = from_coo(cfg["n"], *coo, name=name)
    for a, b, c in zip(ours, (suite.rowptr, suite.colidx, suite.values),
                       (built.rowptr, built.colidx, built.values)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
