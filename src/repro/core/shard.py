"""Multi-device batched SpTRSV: shard the RHS batch axis over a device mesh.

The compiled VLIW instruction stream depends only on L, so the B columns of
a batched solve are embarrassingly parallel: each device runs the identical
instruction-stream pass over its own block of right-hand sides.  This
module places `solve_batch`'s work on a `jax.sharding.Mesh`:

  * instruction-stream constants are closed over by the per-device solve
    function and therefore replicated to every device;
  * the RHS matrix ``b[n, B]`` is sharded over B (all mesh axes flattened,
    see `repro.distributed.sharding.rhs_sharding`) and each device solves
    its local ``[n, B/ndev]`` block under `shard_map` — no collective ever
    runs, the only cross-device traffic is the initial column placement.

Batch widths are padded to ``ndev * pad_batch(ceil(B / ndev))`` so every
device carries the same lane-friendly block; executors are cached per
(program identity, padded per-device width, mesh), so repeated solves —
including nearby batch sizes on the same mesh — never retrace (shared
`executor.trace_count` observability).

    from repro.core import api, shard
    mesh = shard.batch_mesh()                  # 1-D mesh over local devices
    x = api.solve_batch(prog, b, mesh=mesh)    # b[n, B], B over devices
    solver = api.make_solver(prog, batch=B, mesh=mesh)   # cached closure

Tests force a multi-device CPU host via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

from __future__ import annotations

import weakref

import numpy as np

import jax
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import rhs_sharding

from .executor import batched_entry, build_solve_cols, pad_batch, validate_backend
from .program import Program

__all__ = ["batch_mesh", "make_sharded_solver", "sharded_widths"]

# prog -> {(per-device width, mesh) -> jitted shard_map solve}
_SHARD_CACHE: "weakref.WeakKeyDictionary[Program, dict]" = weakref.WeakKeyDictionary()


def batch_mesh(num_devices: int | None = None, axis: str = "batch") -> Mesh:
    """A 1-D mesh over the first ``num_devices`` local devices (default all).

    The axis name is cosmetic — the solver shards the RHS columns over every
    axis of whatever mesh it is given.
    """
    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return Mesh(np.asarray(devs), (axis,))


def sharded_widths(batch: int, mesh: Mesh) -> tuple[int, int]:
    """(per-device padded width, global padded width) for a batch size."""
    ndev = mesh.size
    w_local = pad_batch(-(-batch // ndev))
    return w_local, w_local * ndev


def _build_sharded_executor(prog: Program, w_local: int, mesh: Mesh,
                            backend: str, backend_opts: dict):
    """Jitted `solve(b[n, w_local * ndev]) -> x` mapped over the mesh.

    Each device traces the per-device solver once at the per-device width.
    ``backend="jax"`` maps `executor.build_solve_cols` (instruction
    constants fold into the replicated jaxpr); ``backend="pallas"`` maps
    `repro.kernels.sptrsv.ops.build_solver_cols`, so the kernel's memory
    placements — including the HBM-resident row-blocked large-n regime —
    compose with mesh sharding.  Both per-device solvers start their solve
    state from unsharded zeros (the `lax.scan` carry, the kernel's
    scratch), which the varying-axes check rejects, so the check is off;
    that is sound here because in/out specs are fully sharded over the
    batch axis and the solve never communicates across devices.
    """
    if backend == "pallas":
        from repro.kernels.sptrsv import ops as sptrsv_ops

        solve_local = sptrsv_ops.build_solver_cols(prog, w_local,
                                                   **backend_opts)
    else:
        solve_local = build_solve_cols(prog, w_local)
    spec = P(None, mesh.axis_names)
    return jax.jit(
        jax.shard_map(solve_local, mesh=mesh, in_specs=(spec,),
                      out_specs=spec, check_vma=False)
    )


def _cached_sharded_executor(prog: Program, w_local: int, mesh: Mesh,
                             backend: str, backend_opts: dict):
    per_prog = _SHARD_CACHE.get(prog)
    if per_prog is None:
        per_prog = {}
        _SHARD_CACHE[prog] = per_prog
    key = (w_local, mesh, backend, tuple(sorted(backend_opts.items())))
    fn = per_prog.get(key)
    if fn is None:
        with TraceAnnotation("sptrsv.executor_build"):
            fn = _build_sharded_executor(prog, w_local, mesh, backend,
                                         backend_opts)
        per_prog[key] = fn
    return fn


def make_sharded_solver(prog: Program, batch: int, mesh: Mesh,
                        backend: str = "jax", **backend_opts):
    """Cached `solver(b[n, batch]) -> x[n, batch]` sharded over ``mesh``.

    Pads the batch axis to ``ndev * pad_batch(ceil(batch / ndev))``, places
    the columns with `rhs_sharding`, and runs the per-device executor under
    `shard_map`.  Reuses one trace per (program, per-device width, mesh,
    backend knobs).  ``backend="pallas"`` runs the TPU kernel per device
    (knobs as in `executor.make_pallas_executor`).
    """
    if batch < 0:
        raise ValueError(f"batch must be non-negative, got {batch}")
    validate_backend(backend, backend_opts)
    w_local, width = sharded_widths(max(batch, 1), mesh)
    core = _cached_sharded_executor(prog, w_local, mesh, backend,
                                    backend_opts)
    placement = rhs_sharding(mesh)
    return batched_entry(core, prog.n, batch, width,
                         place=lambda b: jax.device_put(b, placement))
