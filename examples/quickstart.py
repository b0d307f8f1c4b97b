"""Quickstart: solve a sparse triangular system on the modeled accelerator.

    PYTHONPATH=src python examples/quickstart.py

Demonstrates the public API end to end: generate a benchmark matrix,
compile it with the medium-granularity dataflow (paper §IV), execute the
VLIW program with the JAX executor AND the Pallas kernel, and print the
paper's metrics.
"""

import numpy as np

from repro.core import api
from repro.core.csr import random_rhs, serial_solve
from repro.kernels.sptrsv import ops as sptrsv_kernel


def main() -> None:
    # 1. a circuit-simulation-style benchmark matrix (add20 archetype)
    mat = api.matrix("ckt_add20")
    print(f"matrix {mat.name}: n={mat.n} nnz={mat.nnz} "
          f"flops={mat.binary_nodes}")

    # 2. compile: medium granularity dataflow + psum caching + ICR
    prog = api.compile(mat)
    print("compiled:", {k: v for k, v in api.report(prog).items()
                        if k in ("cycles", "throughput_gops", "peak_gops",
                                 "pe_utilization", "compile_s")})

    # 3. solve Lx = b three ways and check against the serial oracle
    b = random_rhs(mat, seed=42)
    x_ref = serial_solve(mat, b)
    x_jax = api.solve(prog, b)                      # lax.scan executor
    x_pal = sptrsv_kernel.solve(prog, b)            # Pallas kernel
    print("jax executor   max err:", float(np.abs(x_jax - x_ref).max()))
    print("pallas kernel  max err:", float(np.abs(x_pal - x_ref).max()))

    # 4. batched multi-RHS: one instruction-stream pass solves all columns
    B = 8
    rng = np.random.default_rng(0)
    bmat = rng.standard_normal((mat.n, B))
    x_bat = api.solve_batch(prog, bmat)             # [n, B] in one pass
    refs = np.stack([serial_solve(mat, bmat[:, i]) for i in range(B)], axis=1)
    print(f"batched (B={B})  max err:", float(np.abs(x_bat - refs).max()))
    solver = api.make_solver(prog, batch=B)         # cached: later calls
    x_bat2 = np.asarray(solver(bmat))               # reuse the same trace
    assert np.allclose(x_bat, x_bat2)

    # 5. multi-device: shard the RHS columns over every local device
    #    (run with XLA_FLAGS=--xla_force_host_platform_device_count=8 to
    #    see it spread over 8 fake CPU devices; on TPU it just works)
    from repro.core import shard
    mesh = shard.batch_mesh()                       # 1-D mesh, all devices
    x_sh = api.solve_batch(prog, bmat, mesh=mesh)   # columns over devices
    print(f"sharded over {mesh.size} device(s) max err:",
          float(np.abs(x_sh - refs).max()))

    # 6. large n: where x cannot fit a core's VMEM the Pallas kernel keeps
    #    x and b in HBM and slides a row window over them (a VMEM ring
    #    filled from b and flushed to x as blocks run, DESIGN.md §1).
    #    Forced here on a small band so it runs quickly; placement="auto"
    #    picks it by itself past a core's VMEM.
    band = api.matrix("band_cz")
    bprog = api.compile(band)
    solver_big = api.make_solver(bprog, batch=B, backend="pallas",
                                 placement="blocked")
    print(f"row-blocked solve: window={solver_big.plan.window} rows "
          f"(of n={band.n}), stride={solver_big.plan.stride}, "
          f"{solver_big.plan.num_blocks} blocks")
    bb = rng.standard_normal((band.n, B))
    x_blk = np.asarray(solver_big(bb))
    refs_b = np.stack([serial_solve(band, bb[:, i]) for i in range(B)], axis=1)
    print("row-blocked      max err:", float(np.abs(x_blk - refs_b).max()))

    # 7. compare the three dataflows of the paper (Fig. 6 / Fig. 9a)
    coarse = api.baseline_coarse(mat).stats
    fine = api.baseline_fine(mat)
    print(f"cycles: coarse={coarse.cycles} fine={fine.effective_cycles:.0f} "
          f"medium={prog.stats.cycles}  (lower is better)")


if __name__ == "__main__":
    main()
