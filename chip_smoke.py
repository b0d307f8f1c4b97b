#!/usr/bin/env python3
"""Smoke test of the SpTRSV solve path on a TPU, through the public API.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # RHS columns sharded over four chips

One chip: the VMEM-resident Pallas kernel on ``ckt_add20`` (n=2,395), the
row-blocked kernel on ``band_huge64k`` (n=65,536, forced: its x fits a
core's VMEM, so ``placement="auto"`` would keep it resident), then a
resilient `SolveService` answering requests for both matrices.  Four chips: ``api.solve_batch(..., mesh=...)`` shards
the RHS columns over the chips with the ``jax`` and the ``pallas``
backends and is compared with the one-device solve of the same columns;
no other phase runs.

Every answer is checked against the float64 serial solve
(`csr.serial_solve`).  Matrices and right-hand sides are generated from
seeds (`core.matrices`).  The script exits non-zero and prints no result
unless JAX's devices are TPUs; a Pallas kernel in interpret mode, a
service incident, a retry or a step down the backend ladder is a failure.
The last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

B = 16
SEED = 0
# Max over columns of ||x - x_ref||_inf / ||x_ref||_inf.  The kernels run in
# float32 (eps 6e-8) and the generated matrices are diagonally dominant, so
# rounding does not grow along the solve: on the CPU the lax.scan executor
# and the interpret-mode kernel reach 1.0e-7 on ckt_add20 and 1.5e-7 on
# band_huge64k.  1e-5 leaves a 65x margin; a wrong or missing row gives O(1).
TOL = 1e-5
SERVICE_REQUESTS = 40


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def tpu_device() -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        fail(f"JAX found platform {d.platform!r} ({d.device_kind}), not a "
             f"TPU; this smoke test runs only on the chip")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def reference(mat, bmat):
    import numpy as np

    from repro.core.csr import serial_solve

    return np.stack([serial_solve(mat, bmat[:, j])
                     for j in range(bmat.shape[1])], axis=1)


def rel_err(x, ref) -> float:
    import numpy as np

    x = np.asarray(x, np.float64).reshape(ref.shape)
    return float((np.abs(x - ref).max(axis=0)
                  / np.abs(ref).max(axis=0)).max())


def check(label: str, x, ref) -> float:
    err = rel_err(x, ref)
    if not err <= TOL:
        fail(f"{label}: max rel err {err!r} > {TOL}")
    return err


def report(phase: str, **fields) -> None:
    kv = " ".join(f"{k}={v!r}" if isinstance(v, float) else f"{k}={v}"
                  for k, v in fields.items())
    print(f"[{phase}] {kv}", flush=True)


def pallas_phase(name: str, placement: str, expect: str) -> tuple:
    """Compile ``name``, solve B columns through `api.solve_batch` with the
    Pallas backend, check the placement taken and the answer."""
    import numpy as np

    from repro.core import api
    from repro.core.matrices import generate

    t0 = time.perf_counter()
    mat = generate(name)
    prog = api.compile(mat)
    compile_s = time.perf_counter() - t0
    b = np.random.default_rng(SEED).standard_normal((mat.n, B))
    opts = dict(backend="pallas", placement=placement, interpret=False)
    solver = api.make_solver(prog, batch=B, **opts)
    if solver.interpret is not False:
        fail(f"{name}: the Pallas kernel would run in interpret mode")
    if solver.placement != expect:
        fail(f"{name}: placement={placement!r} picked {solver.placement!r}, "
             f"expected {expect!r}")
    t0 = time.perf_counter()
    x = api.solve_batch(prog, b, **opts)  # first call: kernel compile + solve
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    solver(b).block_until_ready()
    steady_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = reference(mat, b)
    ref_s = time.perf_counter() - t0
    err = check(f"{name}/{expect}", x, ref)
    plan = solver.plan
    report(f"{name}/{expect}", n=mat.n, cycles=prog.cycles, B=B,
           placement=solver.placement,
           window=plan.window if plan else None,
           host_compile_s=compile_s, first_call_s=first_s,
           steady_solve_s=steady_s, reference_s=ref_s, max_rel_err=err)
    return mat, err


def service_phase(mats: dict) -> float:
    """A resilient pallas `SolveService` answers SERVICE_REQUESTS requests
    over both matrices with no incident, retry or step-down."""
    import numpy as np

    from repro.core import api, executor, serve
    from repro.core.resilience import ResilienceConfig

    svc = api.make_service(mats, backend="pallas", resilience=ResilienceConfig(),
                           clock=serve.ManualClock(), timer=time.perf_counter,
                           max_batch=B, interpret=False)
    rng = np.random.default_rng(SEED + 1)
    ids = sorted(mats)
    sent = []
    t0 = time.perf_counter()
    for i in range(SERVICE_REQUESTS):
        mid = ids[i % len(ids)]
        k = 1 + (i // len(ids)) % 3
        b = rng.standard_normal((mats[mid].n, k))
        sent.append((mid, b, svc.submit(mid, b[:, 0] if k == 1 else b)))
    svc.drain()
    serve_s = time.perf_counter() - t0
    st = svc.stats
    if len(svc.incidents):
        fail(f"service incidents: {svc.incidents.to_list()}")
    if st.retries or st.degraded_flushes or st.failed_flushes \
            or st.requests_shed or st.deadline_failed_columns:
        fail(f"service degraded: {st.to_dict()}")
    stages = {f.stage for f in st.flushes}
    if stages != {"pallas-blocked"}:
        fail(f"service answered from ladder rungs {sorted(stages)}")
    for mid in ids:
        prog = svc.cache.get(mats[mid])
        keys = executor.cached_entries(prog)
        if not keys or any(not isinstance(k, tuple) or k[0] != "pallas"
                           or k[-1] is not False for k in keys):
            fail(f"{mid}: executors {keys} are not all native Pallas")
    err = 0.0
    for mid, b, ticket in sent:
        err = max(err, check(f"service/{mid}", ticket.result(),
                             reference(mats[mid], b)))
    report("service", requests=len(sent), columns=st.completed_columns,
           flushes=st.flush_count(), widths=sorted({f.padded
                                                    for f in st.flushes}),
           incidents=len(svc.incidents), serve_wall_s=serve_s,
           flush_service_s=[round(f.service_s, 4) for f in st.flushes],
           max_rel_err=err)
    return err


def four_chip_phase() -> None:
    """RHS columns over a 4-device mesh vs the one-device solve."""
    import jax
    import numpy as np

    from repro.core import api, shard
    from repro.core.matrices import generate

    if len(jax.devices()) != 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(jax.devices())}")
    mesh = shard.batch_mesh(4)
    width = 4 * B
    cases = [("ckt_add20", "jax", {}),
             ("ckt_add20", "pallas", {"interpret": False}),
             ("band_huge64k", "pallas", {"interpret": False})]
    mats = {}
    for name, backend, opts in cases:
        if name not in mats:
            mat = generate(name)
            b = np.random.default_rng(SEED).standard_normal((mat.n, width))
            mats[name] = (mat, api.compile(mat), b, reference(mat, b))
        mat, prog, b, ref = mats[name]
        sharded = api.make_solver(prog, batch=width, mesh=mesh,
                                  backend=backend, **opts)
        t0 = time.perf_counter()
        x = sharded(b)
        x.block_until_ready()
        first_s = time.perf_counter() - t0
        devices = {s.device for s in x.addressable_shards}
        if len(devices) != 4:
            fail(f"{name}/{backend}: columns landed on {len(devices)} "
                 f"device(s), not 4")
        t0 = time.perf_counter()
        sharded(b).block_until_ready()
        steady_s = time.perf_counter() - t0
        one = api.solve_batch(prog, b, backend=backend, **opts)
        err = check(f"{name}/{backend}/mesh", x, ref)
        check(f"{name}/{backend}/one-device", one, ref)
        diff = float(np.abs(np.asarray(x) - one).max())
        report(f"four-chip {name}/{backend}", n=mat.n, B=width,
               devices=len(devices), first_call_s=first_s,
               steady_solve_s=steady_s, max_rel_err=err,
               max_abs_diff_vs_one_device=diff)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded phase")
    args = ap.parse_args()

    device = tpu_device()
    from repro.core.compile_cache import enable_compile_cache

    report("setup", device=device["kind"], devices=device["count"],
           compile_cache=enable_compile_cache())
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        small, _ = pallas_phase("ckt_add20", "resident", "resident")
        large, _ = pallas_phase("band_huge64k", "blocked", "blocked")
        service_phase({"ckt_add20": small, "band_huge64k": large})
    report("done", total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
