"""Helpers shared by every Pallas kernel in the package."""

from __future__ import annotations

import jax

__all__ = ["LANES", "VMEM_CAPACITY", "VMEM_HEADROOM", "default_interpret",
           "resident_fits", "resident_rows", "resident_state_bytes",
           "resolve_interpret", "tiled_bytes"]

# Mosaic lays a 2-D f32 VMEM array out in (8, 128) tiles.
SUBLANES, LANES = 8, 128
# Physical VMEM of one TPU v5e TensorCore: no kernel's solve state can pass
# it, whatever its scoped limit.  It lives here, away from the Pallas
# imports, so the compiler can read it cheaply.
VMEM_CAPACITY = 128 << 20
# What a kernel asks Mosaic for on top of its solve state, for Mosaic's own
# internal scratch (`sptrsv.kernel.vmem_limit`).
VMEM_HEADROOM = 4 << 20


def tiled_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of a ``[rows, cols]`` f32 array in (8, 128) tiles."""
    return (-(-rows // SUBLANES) * SUBLANES) * (-(-cols // LANES) * LANES) * 4


def resident_rows(n: int) -> int:
    """Rows of the resident SpTRSV kernel's x buffer for ``n`` unknowns: n
    and at least one spare row on top, which takes the x stores of words
    that finalize nothing."""
    return -(-(n + 1) // SUBLANES) * SUBLANES


def resident_state_bytes(n_pad: int, nb: int) -> int:
    """VMEM bytes of the resident SpTRSV kernel: the x buffer (b in place);
    the running sum is a loop value, not a ref."""
    return tiled_bytes(n_pad, nb)


def resident_fits(n: int, nb: int = 1, budget: int | None = None) -> bool:
    """Whether the resident SpTRSV kernel's x buffer for ``n`` unknowns and
    ``nb`` columns fits ``budget`` bytes of solve state; by default what a
    core's VMEM leaves past `VMEM_HEADROOM`, i.e. whether the scoped limit
    the kernel asks for fits the core.  The one rule for all sides of it:
    the compiler relabels rows by level when one column does not fit
    (`core.compiler.order`), ``auto`` placement goes blocked past it, and a
    compiled resident solve that would need it is refused
    (`sptrsv.ops.resolve_placement`, `sptrsv.ops.build_solver_cols`)."""
    if budget is None:
        budget = VMEM_CAPACITY - VMEM_HEADROOM
    return resident_state_bytes(resident_rows(n), nb) <= budget


def default_interpret() -> bool:
    """Auto-detect: compile natively on TPU, interpret elsewhere."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the kernels' ``interpret: bool | None = None`` convention."""
    return default_interpret() if interpret is None else bool(interpret)
