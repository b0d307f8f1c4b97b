"""Helpers shared by every Pallas kernel in the package."""

from __future__ import annotations

import jax

__all__ = ["LANES", "VMEM_CAPACITY", "default_interpret", "resident_fits",
           "resident_rows", "resident_state_bytes", "resolve_interpret",
           "tiled_bytes"]

# Mosaic lays a 2-D f32 VMEM array out in (8, 128) tiles.
SUBLANES, LANES = 8, 128
# Physical VMEM of one TPU v5e TensorCore: no kernel's solve state can pass
# it, whatever its scoped limit.  It lives here, away from the Pallas
# imports, so the compiler can read it cheaply.
VMEM_CAPACITY = 128 << 20


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


def resident_fits(n: int, nb: int = 1) -> bool:
    """Whether the resident SpTRSV kernel's x buffer for ``n`` unknowns and
    ``nb`` columns fits a core's VMEM.  The one rule for both sides of it:
    the compiler relabels rows by level when one column does not fit
    (`core.compiler.order`), and a Pallas solve that would need it is
    refused (`sptrsv.ops.build_solver_cols`)."""
    return resident_state_bytes(resident_rows(n), nb) <= VMEM_CAPACITY


def default_interpret() -> bool:
    """Auto-detect: compile natively on TPU, interpret elsewhere."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the kernels' ``interpret: bool | None = None`` convention."""
    return default_interpret() if interpret is None else bool(interpret)
