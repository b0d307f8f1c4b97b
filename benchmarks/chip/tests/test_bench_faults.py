"""A run whose timed path is broken underneath comes out not correct.

These drive `run.run_cell`, everything of a run after its look for a chip,
at a tiny size on the CPU with the program's ``jax`` backend, once sound
and once for each fault a cell can have:

* ``unchanged``: the solve hands back its state untouched (x = b);
* ``half_batch``: half of the batch's columns are left out (zero);
* ``chip_lost``: one chip's block of columns is lost and the block of
  another chip stands in for it (the column placement over the mesh);
* ``altered``: one entry of one answer is changed where it is produced.
"""

import time

import numpy as np
import pytest

from benchmarks.chip import reference, registry, run
from benchmarks.chip.generators import circuit

N = 400
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _config():
    coo = circuit.generate(N, 8, 3.1, 3)
    return {"name": "tiny_circuit", "generator": "circuit", "n": N,
            "hubs": 8, "avg_deg": 3.1, "pattern_seed": 3,
            "generator_params": ["n", "hubs", "avg_deg", "pattern_seed"],
            "limit_max_rel_err": registry.load_json(
                "configs", "ckt_add20")["limit_max_rel_err"],
            "fingerprint": reference.fingerprint(
                *reference.csr_arrays(N, *coo))}


def _cell(traffic, metrics):
    return registry.Cell(name="tiny", chips=1, config=_config(),
                         traffic=dict(traffic, backend="jax", name="t"),
                         end_to_end=tuple({"name": m, "unit": "-"}
                                          for m in metrics),
                         per_layer=())


CLOSED = {"driver": "closed_batch", "batch": 8, "pool": 3, "keep": 8}
CLOSED_METRICS = ("solves_per_s", "setup_s")


def _break(x, fault):
    x = np.array(x, copy=True)
    k = x.shape[1]
    if fault == "half_batch":
        x[:, k // 2:] = 0.0
    elif fault == "chip_lost":
        q = max(1, k // 4)  # four chips: block 1 comes back as block 0
        x[:, q:2 * q] = x[:, :q]
    elif fault == "altered":
        x[N // 2, 0] += 1.0
    return x


def _run(cell):
    out, checks = run.run_cell(cell, 2**31 + 77, 0.3, False, DEVICE,
                               t_start=time.perf_counter())
    return out


def test_closed_sound_run_is_correct():
    out = _run(_cell(CLOSED, CLOSED_METRICS))
    assert out["correct"] and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == set(CLOSED_METRICS)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "chip_lost",
                                   "altered"])
def test_closed_fault_is_not_correct(fault, monkeypatch):
    from repro.core import api

    sound = api.solve_batch

    def broken(prog, b, **kw):
        if fault == "unchanged":
            return np.asarray(b)
        return _break(sound(prog, b, **kw), fault)

    monkeypatch.setattr(api, "solve_batch", broken)
    out = _run(_cell(CLOSED, CLOSED_METRICS))
    assert out["correct"] is False
    assert out["checks"]["max_rel_err"]["value"] > \
        out["checks"]["max_rel_err"]["limit"]
