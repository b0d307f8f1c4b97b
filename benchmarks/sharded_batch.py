"""Sharded batched multi-RHS: solves/sec vs device count, fixed per-device B.

The batch axis of `api.solve_batch` is embarrassingly parallel, so weak
scaling over devices (B = ndev * B_PER_DEVICE) should hold solve latency
roughly flat while total solves/sec grows with the device count.  One
process drives every device count over sub-meshes of its own devices
(`shard.batch_mesh(k)`): a chip belongs to one process, so no child process
is started.  On a CPU host, ``python -m benchmarks.sharded_batch`` fakes
8 devices (the flag must precede JAX's initialization); under
``benchmarks.run`` the counts are those of the devices JAX already has.
"""

from __future__ import annotations

import os

DEVICE_COUNTS = [1, 2, 4, 8]
B_PER_DEVICE = 16
MATRICES = ["band_cz", "ckt_add20"]


def run() -> list[dict]:
    import numpy as np

    import jax

    from repro.core import api, shard
    from repro.core.matrices import generate

    from .common import timeit

    counts = [k for k in DEVICE_COUNTS if k <= len(jax.devices())]
    rows = []
    for name in MATRICES:
        mat = generate(name)
        prog = api.compile(mat)
        flops = 2 * mat.nnz - mat.n
        for ndev in counts:
            mesh = shard.batch_mesh(ndev)
            B = ndev * B_PER_DEVICE
            bmat = np.random.default_rng(0).standard_normal(
                (mat.n, B)).astype(np.float32)
            sharded = api.make_solver(prog, batch=B, mesh=mesh)
            local = api.make_solver(prog, batch=B)
            t_sh = timeit(lambda: np.asarray(sharded(bmat)))
            t_lo = timeit(lambda: np.asarray(local(bmat)))
            rows.append({
                "name": name,
                "devices": ndev,
                "batch": B,
                "sharded_solves_per_s": round(B / t_sh, 1),
                "single_device_solves_per_s": round(B / t_lo, 1),
                "sharded_gops": round(B * flops / t_sh / 1e9, 4),
                "sharded_us_per_call": round(t_sh * 1e6, 1),
            })
    return rows


def main() -> None:
    from .common import emit

    rows = run()
    emit(rows, "sharded_batch")
    for name in MATRICES:
        per = {r["devices"]: r["sharded_solves_per_s"]
               for r in rows if r["name"] == name}
        base = per[min(per)]
        scale = " ".join(f"{d}dev={per[d] / base:.2f}x" for d in sorted(per))
        print(f"# {name}: solves/sec vs 1 device: {scale}")


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
