"""Large-n scaling: solves/sec of the Pallas memory placements vs n.

The VMEM-resident Pallas kernel needs the whole ``x[n_pad, B]`` +
``b[n_pad, B]`` solve state on-chip, capping solvable n well below the
paper's 85k-node DAGs on a real TPU.  The row-blocked placement keeps x/b
in HBM behind a sliding VMEM window (`kernels/sptrsv/ops.plan_window`), so
its VMEM footprint is set by the window, not by n.  This sweep walks a
banded-matrix size ladder and records, per n:

  * solves/sec of the batched JAX `lax.scan` executor (reference),
  * solves/sec of the Pallas kernel in ``resident`` and ``blocked``
    placements (same batch width, same cached-executor discipline),
  * the planned window/stride and the VMEM solve-state bytes of each
    placement — the memory ratio is the point of the exercise.

On a CPU host both Pallas placements run in interpreter mode (auto-detect),
so their wall-clock is a correctness/overlap proxy; re-run on a real TPU
slice for kernel numbers.  ``BENCH_LARGE_N=band_wide4k,band_big16k`` picks
the ladder (default stops at 16k; add ``band_huge64k`` for the paper-scale
rung — its compile alone takes ~1 min).
"""

from __future__ import annotations

import os

import numpy as np

from repro.core import api
from repro.core.executor import make_jax_executor, make_pallas_executor
from repro.kernels.sptrsv import ops as sptrsv_ops

from .common import emit, timeit

DEFAULT_LADDER = ["band_cz", "band_wide4k", "band_big16k"]
BATCH = 16


def main() -> None:
    ladder = [s for s in os.environ.get(
        "BENCH_LARGE_N", ",".join(DEFAULT_LADDER)).split(",") if s]
    rows = []
    rng = np.random.default_rng(0)
    for name in ladder:
        mat = api.matrix(name)
        prog = api.compile(mat)
        bmat = rng.standard_normal((mat.n, BATCH)).astype(np.float32)
        plan = sptrsv_ops.plan_window(prog)
        if not plan.feasible:
            print(f"# {name}: blocked placement infeasible ({plan.reason})")
            continue

        jax_solver = make_jax_executor(prog, batch=BATCH)
        solvers = {"jax_scan": jax_solver}
        for placement in ("resident", "blocked"):
            solvers[placement] = make_pallas_executor(
                prog, batch=BATCH, placement=placement,
            )

        row = {
            "name": name, "n": mat.n, "nnz": mat.nnz, "batch": BATCH,
            "window": plan.window, "stride": plan.stride,
            "num_blocks": plan.num_blocks,
            # tiled (8, 128) VMEM bytes of the solve state
            "resident_state_bytes": sptrsv_ops.state_bytes(
                prog, BATCH, placement="resident")["xb"],
            "blocked_state_bytes": plan.state_bytes(BATCH),
            # either kernel's double-buffered instruction SMEM of
            # row-serial entries
            "instr_buffer_bytes": sptrsv_ops.instr_buffer_bytes(prog),
            "instr_traffic_kib": round(prog.instr_bytes() / 1024, 1),
        }
        for label, solver in solvers.items():
            dt = timeit(lambda: np.asarray(solver(bmat)))
            row[f"{label}_solves_per_s"] = round(BATCH / dt, 1)
            row[f"{label}_us_per_call"] = round(dt * 1e6, 1)
        rows.append(row)
    emit(rows, "large_n")


if __name__ == "__main__":
    main()
