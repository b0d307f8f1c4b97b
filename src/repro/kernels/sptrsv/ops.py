"""Compiler-side wrapper: run a compiled `Program` through the Pallas kernel.

Two memory placements for the solve state (DESIGN.md §1):

  * ``resident`` — x and b live in VMEM for the whole solve
    (`kernel.sptrsv_pallas`); fastest while both fit.
  * ``blocked``  — x and b stay in HBM and the kernel slides a row-blocked
    VMEM window over them (`kernel.sptrsv_pallas_blocked`), flushing and
    shifting it at cycle-block boundaries with async DMA.  This is the
    large-n path: VMEM use is bounded by the window, not by n.

``placement="auto"`` (the default) picks per solve: resident while the
x+b footprint is under ``vmem_limit_bytes``, blocked beyond it whenever the
program's row-access envelope admits a sliding window (`plan_window`).
Footprints are counted as the chip lays them out: a ``[rows, B]`` f32 array
takes whole (8, 128) tiles (`kernel.tiled_bytes`), so at B=16 it occupies
8x its logical bytes.

The wrapper performs the compiler-side data staging the hardware's stream
memory provides: values are pre-gathered per instruction word so the kernel
streams them sequentially (no positional indirection, as in the paper's
stream-memory design), and the compiler's packed instruction words
(``Program.instr``, ``[T, planes, P]`` int32 — DESIGN.md §Perf,
"Instruction encoding") are padded to the cycle-block multiple and
flattened, so each block arrives in SMEM with a single DMA.  Per
lane-cycle the kernel streams ``4 * planes + 4`` bytes (8 B in the
single-plane regime) instead of the 24 B the historical five unpacked
planes cost.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.errors import PlacementInfeasibleError
from repro.core.executor import _psum_slots, as_batch
from repro.core.program import Program

from repro.kernels.common import resolve_interpret

from .kernel import (
    blocked_state_bytes,
    resident_state_bytes,
    sptrsv_pallas,
    sptrsv_pallas_blocked,
    tiled_bytes,
)

__all__ = [
    "solve",
    "plan_window",
    "resolve_placement",
    "build_solver_cols",
    "instr_buffer_bytes",
    "state_bytes",
    "WindowPlan",
    "DEFAULT_STATE_BYTES",
]

# auto-placement threshold for the tiled VMEM x+b solve-state footprint.
# A TPU v5e kernel gets 16 MiB of scoped VMEM by default, shared with the
# feedback/psum lane state and Mosaic's own scratch; 4 MiB of x+b keeps the
# resident kernel inside it.  Overridable per call (``vmem_limit_bytes``).
DEFAULT_STATE_BYTES = 4 << 20

_ROW_ALIGN = 8  # window/stride row granularity (f32 sublane tile)


@dataclasses.dataclass(frozen=True)
class WindowPlan:
    """A feasible sliding-window placement for the blocked kernel.

    Cycle block g executes against x/b rows ``[g*stride, g*stride +
    window)``; ``n_hbm`` is the padded HBM row count covering the full
    window sweep.  ``feasible=False`` carries a human-readable ``reason``
    (the auto path then falls back to the VMEM-resident placement).
    """

    feasible: bool
    stride: int = 0
    window: int = 0
    n_hbm: int = 0
    num_blocks: int = 0
    reason: str = ""

    def state_bytes(self, nb: int) -> int:
        """Tiled VMEM bytes of the double-buffered x+b windows."""
        return 4 * tiled_bytes(self.window, nb)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def plan_window(
    prog: Program,
    cycles_per_block: int = 128,
    min_window: int | None = None,
) -> WindowPlan:
    """Derive a (stride, window) pair from the program's row-range metadata.

    The compiler records, per cycle, the min/max solution row any active
    lane touches (`Program.row_lo/row_hi`).  Reducing those over each cycle
    block gives the block's touched-row envelope ``[lo_g, hi_g]``; the
    window for block g is placed at base ``g * stride``, so feasibility
    requires ``g*stride <= lo_g`` and ``hi_g < g*stride + window`` for all
    g.  The stride is maximized (smallest window), then the window sized to
    the worst block — both rounded to the f32 sublane granularity.

    Programs whose row envelope does not advance monotonically enough
    (e.g. circuit matrices with hub columns read across the whole DAG)
    yield ``feasible=False``; such DAGs genuinely need the whole x vector
    live and must use the resident placement.
    """
    if prog.row_lo is None or prog.row_hi is None:
        return WindowPlan(False, reason="program has no row-range metadata "
                                        "(recompile with this version)")
    t = prog.cycles
    g = -(-t // cycles_per_block)
    lo = np.full(g * cycles_per_block, prog.n, dtype=np.int64)
    hi = np.full(g * cycles_per_block, -1, dtype=np.int64)
    lo[:t] = prog.row_lo
    hi[:t] = prog.row_hi
    lo = lo.reshape(g, cycles_per_block).min(axis=1)
    hi = hi.reshape(g, cycles_per_block).max(axis=1)
    nonempty = hi >= 0

    stride = prog.n
    for gi in range(1, g):
        if nonempty[gi]:
            stride = min(stride, int(lo[gi]) // gi)
    stride -= stride % _ROW_ALIGN
    if g > 1 and stride <= 0:
        return WindowPlan(False, reason="row envelope not monotone: an "
                                        "early row stays live across the "
                                        "whole schedule")
    if g == 1:
        stride = _ROW_ALIGN  # unused by a single-block sweep, but traced

    w_req = 0
    for gi in range(g):
        if nonempty[gi]:
            w_req = max(w_req, int(hi[gi]) - gi * stride + 1)
    window = max(w_req, 2 * stride, min_window or 0, 2 * _ROW_ALIGN)
    window = _round_up(window, _ROW_ALIGN)
    n_hbm = (g - 1) * stride + window
    return WindowPlan(True, stride=stride, window=window, n_hbm=n_hbm,
                      num_blocks=g)


def resolve_placement(
    prog: Program,
    nb: int,
    *,
    placement: str = "auto",
    vmem_limit_bytes: int | None = None,
    cycles_per_block: int = 128,
    x_block_rows: int | None = None,
) -> tuple[str, WindowPlan | None]:
    """Pick ``("resident", None)`` or ``("blocked", plan)`` for a solve.

    ``placement`` forces a regime (``"blocked"`` raises if the program's
    row envelope admits no window); ``"auto"`` compares the VMEM-resident
    x+b footprint for ``nb`` RHS columns against ``vmem_limit_bytes``
    (``None`` -> `DEFAULT_STATE_BYTES`) and only goes blocked when that
    saves memory and a window exists.  ``x_block_rows`` floors the planned
    window (perf knob; the planner still enlarges it to whatever the
    schedule requires).
    """
    if vmem_limit_bytes is None:
        vmem_limit_bytes = DEFAULT_STATE_BYTES
    if placement == "resident":
        return "resident", None
    if placement not in ("auto", "blocked"):
        raise ValueError(f"unknown placement {placement!r}")
    plan = plan_window(prog, cycles_per_block, min_window=x_block_rows)
    if placement == "blocked":
        if not plan.feasible:
            # taxonomy leaf (DESIGN.md §7); still a ValueError for
            # pre-taxonomy callers, and the fallback ladder treats it as
            # "this rung cannot serve this program" and degrades
            raise PlacementInfeasibleError(
                f"row-blocked placement infeasible: {plan.reason}",
                detail={"reason": plan.reason})
        return "blocked", plan
    resident_bytes = 2 * tiled_bytes(_resident_rows(prog), nb)
    if resident_bytes <= vmem_limit_bytes or not plan.feasible:
        return "resident", None
    if plan.state_bytes(nb) >= resident_bytes:
        return "resident", None  # window as big as the vector: no point
    return "blocked", plan


def _resident_rows(prog: Program) -> int:
    return _round_up(prog.n, _ROW_ALIGN)


def _pad_to(arr: np.ndarray, t_pad: int, fill=0) -> np.ndarray:
    t = arr.shape[0]
    if t == t_pad:
        return arr
    out = np.full((t_pad,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[:t] = arr
    return out


def _stage_instructions(prog: Program, cycles_per_block: int):
    """Pad the packed instruction words and pre-gather the stream values.

    The program already carries the packed ``[T, planes, P]`` words — the
    pack happens once at compile time; staging only pads to the cycle-block
    multiple (pad rows are the all-NOP word 0), gathers the f32 values per
    instruction slot so the kernel streams them positionally, and flattens
    both (cycle-major, then plane, then lane) for the kernel's 1-D SMEM
    buffers.
    """
    t = prog.cycles
    t_pad = _round_up(t, cycles_per_block)
    values = prog.stream[prog.val_idx]          # [T, P] pre-gathered
    instr = _pad_to(prog.instr, t_pad)          # [T_pad, planes, P]
    return (instr.reshape(-1),
            _pad_to(values.astype(np.float32), t_pad).reshape(-1))


def instr_buffer_bytes(prog: Program, cycles_per_block: int = 128) -> int:
    """SMEM bytes of the kernel's double-buffered instruction streaming.

    Two cycle-block buffers of packed words plus two of pre-gathered f32
    values: ``2 * tb * P * (4 * planes + 4)`` — halved-plus by the packed
    single-word encoding (planes=1: 8 B per buffered lane-cycle vs the 24 B
    of the historical five-plane layout).  A TPU v5e core has 1 MiB of SMEM.
    """
    return 2 * cycles_per_block * prog.num_cus * (4 * prog.planes + 4)


def state_bytes(prog: Program, nb: int, *, placement: str,
                plan: WindowPlan | None = None,
                cycles_per_block: int = 128) -> dict:
    """Memory accounting of one Pallas solve, in tiled bytes.

    Returns ``{"xb", "vmem", "instr"}`` for ``nb`` RHS columns under
    ``placement`` (``"blocked"`` needs the `WindowPlan`): ``xb`` is the
    x+b solve state, ``vmem`` all of the kernel's VMEM (x+b plus the
    feedback/psum lane state), ``instr`` the SMEM instruction buffers.
    """
    slots = _psum_slots(prog)
    if placement == "blocked":
        if plan is None or not plan.feasible:
            raise ValueError("blocked accounting needs a feasible WindowPlan")
        xb = plan.state_bytes(nb)
        vmem = blocked_state_bytes(plan.window, nb, prog.num_cus, slots)
    elif placement == "resident":
        rows = _resident_rows(prog)
        xb = 2 * tiled_bytes(rows, nb)
        vmem = resident_state_bytes(rows, nb, prog.num_cus, slots)
    else:
        raise ValueError(f"unknown placement {placement!r}")
    return {"xb": xb, "vmem": vmem,
            "instr": instr_buffer_bytes(prog, cycles_per_block)}


def build_solver_cols(
    prog: Program,
    width: int,
    *,
    cycles_per_block: int = 128,
    placement: str = "auto",
    vmem_limit_bytes: int | None = None,
    x_block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Build an unjitted ``solve(b[n, width]) -> x[n, width]`` closure.

    Stages the instruction tensors once (device-resident across calls),
    resolves the memory placement, and returns a closure suitable for the
    per-(program, knobs) executor cache (`executor.make_pallas_executor`).
    The chosen regime is exposed as ``closure.placement`` /
    ``closure.plan``, and the resolved interpreter flag as
    ``closure.interpret``, for tests and diagnostics.
    """
    mode, plan = resolve_placement(
        prog, width, placement=placement, vmem_limit_bytes=vmem_limit_bytes,
        cycles_per_block=cycles_per_block, x_block_rows=x_block_rows,
    )
    instr_np, values_np = _stage_instructions(prog, cycles_per_block)
    instr = jnp.asarray(instr_np)
    values = jnp.asarray(values_np)
    n = prog.n
    n_slots = _psum_slots(prog)
    n_rows = _resident_rows(prog) if mode == "resident" else plan.n_hbm
    interpret = resolve_interpret(interpret)
    kw = dict(num_cus=prog.num_cus, planes=prog.planes,
              cycles_per_block=cycles_per_block, num_slots=n_slots,
              interpret=interpret)

    @jax.jit  # fold the pad/slice into the kernel dispatch
    def solve_cols(bmat: jnp.ndarray) -> jnp.ndarray:
        bp = jnp.zeros((n_rows, width), jnp.float32)
        bp = bp.at[:n].set(jnp.asarray(bmat, jnp.float32))
        if mode == "resident":
            x = sptrsv_pallas(instr, values, bp, **kw)
        else:
            x = sptrsv_pallas_blocked(instr, values, bp, window=plan.window,
                                      stride=plan.stride, **kw)
        return x[:n]

    solve_cols.placement = mode
    solve_cols.plan = plan
    solve_cols.interpret = interpret
    return solve_cols


def solve(
    prog: Program,
    b: np.ndarray,
    *,
    cycles_per_block: int = 128,
    interpret: bool | None = None,
    placement: str = "auto",
    vmem_limit_bytes: int = DEFAULT_STATE_BYTES,
    x_block_rows: int | None = None,
) -> np.ndarray:
    """Solve Lx=b by executing `prog` in the Pallas kernel.

    ``b`` may be ``[n]`` (single RHS) or ``[n, B]`` (batched multi-RHS);
    the result has the matching shape.  Batched solves stream the
    instruction tensor once for all B columns; the batch axis is padded to
    a lane-friendly width (`executor.pad_batch`) so nearby widths share one
    compile, and the underlying solver is cached per (program, padded
    width, placement knobs) — repeated solves never retrace.

    ``placement`` selects the memory regime (see module docstring);
    ``interpret=None`` auto-detects: native compile on TPU, interpreter
    elsewhere.
    """
    from repro.core.executor import make_pallas_executor

    bmat, single = as_batch(b)
    solver = make_pallas_executor(
        prog, batch=bmat.shape[1], cycles_per_block=cycles_per_block,
        placement=placement, vmem_limit_bytes=vmem_limit_bytes,
        x_block_rows=x_block_rows, interpret=interpret,
    )
    x = np.asarray(solver(bmat))
    return x[:, 0] if single else x
