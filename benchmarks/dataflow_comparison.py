"""Fig. 9a: throughput of coarse / fine / medium (this work) dataflows.

The medium dataflow here matches the paper's Fig. 9a configuration: ICR on,
psum caching OFF (the caching ablation is Fig. 9b/c -> psum_sweep.py).
"""

from __future__ import annotations

import dataclasses

from repro.core import api
from repro.core.matrices import generate
from repro.core.program import AccelConfig
from repro.core.compiler import compile_program

from .common import FIG9_SET, emit


def run() -> list[dict]:
    rows = []
    base = AccelConfig()
    for name in FIG9_SET:
        mat = generate(name)
        med = compile_program(
            mat, dataclasses.replace(base, psum_cache=False)
        ).stats
        coa = api.baseline_coarse(mat).stats
        fin = api.baseline_fine(mat)
        rows.append({
            "name": name,
            "n": mat.n,
            "nnz": mat.nnz,
            "coarse_cycles": coa.cycles,
            "fine_cycles_eff": round(fin.effective_cycles, 1),
            "medium_cycles": med.cycles,
            "coarse_gops": round(coa.throughput_gops(base), 3),
            "fine_gops": round(fin.throughput_gops(base.clock_mhz), 3),
            "medium_gops": round(med.throughput_gops(base), 3),
            "peak_gops": round(med.peak_throughput_gops(base), 2),
        })
    return rows


def main() -> None:
    emit(run(), "fig9a_dataflow_comparison")


if __name__ == "__main__":
    main()
