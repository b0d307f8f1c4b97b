"""Compiler-side wrapper: run a compiled `Program` through the Pallas kernel.

Two memory placements for the solve state (DESIGN.md §1):

  * ``resident`` — x lives in VMEM for the whole solve, in one buffer that
    b is copied into (`kernel.sptrsv_pallas`); fastest while it fits.
  * ``blocked``  — x and b stay in HBM and the kernel slides a row window
    over them (`kernel.sptrsv_pallas_blocked`), held in a VMEM ring that
    async DMAs fill from b and flush to x as blocks run.  This is the
    large-n path: VMEM use is bounded by the window, not by n.

``placement="auto"`` (the default) picks per solve, by one rule: it goes
blocked only where the resident x buffer does not fit the solve-state
budget (`kernels.common.resident_fits`: by default what a core's VMEM leaves
past Mosaic's headroom, the rule the compiler's level-order pass reads too;
``vmem_limit_bytes`` when one is given), the program admits a sliding window
(`plan_window`), and the window's ring takes less than the resident x.
Footprints are counted as the chip lays them out: a ``[rows, B]`` f32 array
takes whole (8, 128) tiles (`kernels.common.tiled_bytes`), so at B=16 it
occupies 8x its logical bytes.

The wrapper performs the compiler-side data staging the hardware's stream
memory provides: values are pre-gathered per instruction word so the kernel
streams them sequentially (no positional indirection, as in the paper's
stream-memory design), and the compiler's packed instruction words
(``Program.instr``, ``[T, planes, P]`` int32 — DESIGN.md §Perf,
"Instruction encoding") are decoded once into the rows each access uses
(`kernel`'s module docstring) and flattened, so each block arrives in SMEM
with a single DMA per stream.  Both kernels get a row-serial stream of the
words of `_row_chains`, in blocks of `kernel.ROW_BLOCK` entries of ``4 *
ROW_PLANES + 4`` bytes: the resident kernel's (`_stage_rows`) has the rows
in the order of their FINALs, the blocked kernel's (`_stage_blocked`) in
ascending row order, each entry's rows staged into its block's window.
Psum-only words are neither streamed nor executed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.errors import PlacementInfeasibleError
from repro.core.executor import _psum_slots, as_batch, permute_rows
from repro.core.program import (
    OP_FINAL,
    OP_NOP,
    PS_KEEP,
    PS_LOAD,
    PS_STORE_RESET,
    PS_SWAP,
    Program,
    decode_instructions,
)

from repro.kernels.common import (
    VMEM_CAPACITY,
    VMEM_HEADROOM,
    resident_fits,
    resident_rows,
    resident_state_bytes,
    resolve_interpret,
    tiled_bytes,
)

from .kernel import (
    DST,
    ROW_BLOCK,
    ROW_PLANES,
    SEGMENT_ALIGN,
    SRC,
    UNROLL,
    blocked_state_bytes,
    ring_rows,
    sptrsv_pallas,
    sptrsv_pallas_blocked,
)

__all__ = [
    "solve",
    "plan_window",
    "resolve_placement",
    "build_solver_cols",
    "instr_buffer_bytes",
    "state_bytes",
    "Stream",
    "WindowPlan",
]

_ROW_ALIGN = 8  # window/stride row granularity (f32 sublane tile)


@dataclasses.dataclass(frozen=True)
class WindowPlan:
    """A feasible sliding-window placement for the blocked kernel.

    Block g of the blocked stream (`_stage_blocked`) executes against x/b
    rows ``[g*stride, g*stride + window)``; ``n_hbm`` is the padded HBM row
    count covering the full window sweep.  ``feasible=False`` carries a
    human-readable ``reason`` (the auto path then keeps the VMEM-resident
    placement).
    """

    feasible: bool
    stride: int = 0
    window: int = 0
    n_hbm: int = 0
    num_blocks: int = 0
    reason: str = ""

    def state_bytes(self, nb: int) -> int:
        """Tiled VMEM bytes of the kernel's x ring and spare row."""
        return blocked_state_bytes(self.window, self.stride, nb)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _block_len(m: int) -> int:
    """Entries per block of a row-serial stream of ``m`` entries."""
    return min(ROW_BLOCK, _round_up(max(m, 1), SEGMENT_ALIGN))


def plan_window(prog: Program, *, min_window: int | None = None) -> WindowPlan:
    """Derive a (stride, window) pair from the blocked stream's own rows.

    The blocked kernel runs the row-serial stream in ascending row order
    (`_blocked_order`), cut into blocks of ``k`` entries.  Reducing each
    block's x rows gives its row envelope ``[lo_g, hi_g]``; the window for
    block g is placed at base ``g * stride``, so feasibility requires
    ``g*stride <= lo_g`` and ``hi_g < g*stride + window`` for all g.  The
    stride is maximized (smallest window) and rounded down to the f32
    sublane granularity, then the window sized to the worst block, floored
    by ``min_window`` and by twice the stride, and rounded up to a multiple
    of the stride, so that the kernel's ring fills and flushes whole
    strides.

    Programs whose rows do not advance monotonically enough (e.g. circuit
    matrices with hub columns read across the whole DAG) yield
    ``feasible=False``; such DAGs genuinely need the whole x vector live and
    must use the resident placement.
    """
    src = _blocked_order(prog)[2]
    k = _block_len(len(src))
    g = -(-len(src) // k)
    lo = np.minimum.reduceat(src, np.arange(g) * k)
    hi = np.maximum.reduceat(src, np.arange(g) * k)
    if g > 1:
        stride = int((lo[1:] // np.arange(1, g)).min())
        stride -= stride % _ROW_ALIGN
        if stride <= 0:
            return WindowPlan(False, reason="row envelope not monotone: an "
                                            "early row stays live across "
                                            "the whole stream")
    else:
        stride = _ROW_ALIGN  # unused by a single-block sweep, but traced
    w_req = int((hi - np.arange(g) * stride).max()) + 1
    window = max(w_req, 2 * stride, min_window or 0, 2 * _ROW_ALIGN)
    window = _round_up(window, stride)
    n_hbm = (g - 1) * stride + window
    return WindowPlan(True, stride=stride, window=window, n_hbm=n_hbm,
                      num_blocks=g)


def resolve_placement(
    prog: Program,
    nb: int,
    *,
    placement: str = "auto",
    vmem_limit_bytes: int | None = None,
    x_block_rows: int | None = None,
) -> tuple[str, WindowPlan | None]:
    """Pick ``("resident", None)`` or ``("blocked", plan)`` for a solve.

    ``placement`` forces a regime (``"blocked"`` raises if the program's
    rows admit no window); ``"auto"`` goes blocked only when the resident x
    buffer for ``nb`` RHS columns does not fit the solve-state budget
    (`kernels.common.resident_fits`: ``vmem_limit_bytes`` if given, else
    what a core's VMEM leaves past Mosaic's headroom), a window exists, and
    its ring takes less than the resident x.  ``x_block_rows`` floors the
    planned window (perf knob; the planner still enlarges it to whatever
    the stream requires).
    """
    if placement == "resident":
        return "resident", None
    if placement not in ("auto", "blocked"):
        raise ValueError(f"unknown placement {placement!r}")
    if placement == "auto" and resident_fits(prog.n, nb, vmem_limit_bytes):
        return "resident", None
    plan = plan_window(prog, min_window=x_block_rows)
    if placement == "blocked":
        if not plan.feasible:
            # taxonomy leaf (DESIGN.md §7); still a ValueError for
            # pre-taxonomy callers, and the fallback ladder treats it as
            # "this rung cannot serve this program" and degrades
            raise PlacementInfeasibleError(
                f"row-blocked placement infeasible: {plan.reason}",
                detail={"reason": plan.reason})
        return "blocked", plan
    if not plan.feasible or \
            plan.state_bytes(nb) >= tiled_bytes(_resident_rows(prog), nb):
        return "resident", None  # no window, or one as big as the vector
    return "blocked", plan


def _resident_rows(prog: Program) -> int:
    """Rows of the resident x buffer (`kernels.common.resident_rows`)."""
    return resident_rows(prog.n)


@dataclasses.dataclass(frozen=True)
class Stream:
    """A program staged for a kernel: its row-serial entries, in blocks.

    ``instr`` is ``[G, ROW_PLANES, k]`` flattened (the planes `kernel.SRC`
    and `kernel.DST`), ``values`` ``[G, k]`` flattened, ``counts[g]`` the
    entries of block g: blocks of ``k`` entries (`_block_len`), only the
    last one short and padded with the filler entry.
    """

    instr: np.ndarray
    values: np.ndarray
    counts: np.ndarray
    k: int
    slot_words: int  # T * P: the lane slots of the uncompacted grid

    @property
    def stream_words(self) -> int:
        """Entries the kernel executes per solve (counts rounded up to the
        unroll factor, filler included)."""
        return int((-(-self.counts // UNROLL) * UNROLL).sum())


def _row_chains(prog: Program) -> tuple[np.ndarray, np.ndarray]:
    """``(t, lane)`` of the words the row-serial stream runs, in its order.

    Follows each lane's psum control (the mux of `executor.execute_numpy`)
    once.  A lane's active words fall into segments: one starts at a word
    whose psum control is not KEEP, or at the lane's first word, and runs
    over the KEEP words after it.  A segment's running sum starts from zero
    (RESET, STORE_RESET, a lane's first word, a slot never parked into) or
    from the sum parked in its slot (LOAD, SWAP) by the last STORE_RESET or
    SWAP there, which is the parking lane's segment before it.  So the
    chain a FINAL ends is the path of segments from one that started from
    zero to the FINAL's own, and its row runs that path's EDGE words in
    stream order, then the FINAL.  Rows come in the order of their FINALs.
    Psum-only words (op NOP) do no arithmetic and are dropped, as are sums
    that no FINAL ends.  A word that continues a chain after its FINAL
    raises ``ValueError``.
    """
    op, _, ctl, slot = decode_instructions(prog.instr, prog.planes)
    t, lane = np.nonzero((op != OP_NOP) | (ctl != PS_KEEP))  # cycle-major
    op, ctl = op[t, lane], ctl[t, lane]
    slot = np.minimum(slot[t, lane], _psum_slots(prog) - 1)

    # segments, numbered in the order of their first words
    by_lane = np.argsort(lane, kind="stable")
    starts = ctl[by_lane] != PS_KEEP
    starts[np.flatnonzero(np.diff(lane[by_lane], prepend=-1))] = True
    first = by_lane[starts]
    rank = np.empty(len(first), np.int64)
    rank[np.argsort(first)] = np.arange(len(first))
    seg = np.empty(len(t), np.int64)
    seg[by_lane] = rank[np.cumsum(starts) - 1]
    first = np.sort(first)

    # each segment's op words, in stream order; a FINAL ends its segment
    kept = np.flatnonzero(op != OP_NOP)
    kept = kept[np.argsort(seg[kept], kind="stable")]
    kseg = seg[kept]
    size = np.bincount(kseg, minlength=len(first))
    begin = np.cumsum(size) - size
    fin = op[kept] == OP_FINAL
    after = np.flatnonzero(fin & (np.arange(len(kept))
                                  != begin[kseg] + size[kseg] - 1))
    ended = np.zeros(len(first), bool)
    ended[kseg[fin]] = True

    def refuse(w):
        raise ValueError(
            f"word at cycle {t[w]}, lane {lane[w]} continues a running sum "
            f"after the FINAL that ended it")

    if len(after):
        refuse(kept[after[0] + 1])
    parent = np.full(len(first), -1, np.int64)
    last, parked = {}, {}
    for s, (c, ln, q) in enumerate(zip(ctl[first].tolist(),
                                       lane[first].tolist(),
                                       slot[first].tolist())):
        if c in (PS_LOAD, PS_SWAP):
            up = parent[s] = parked.get((ln, q), -1)
            if up >= 0 and ended[up]:
                ended[s] = True     # a dead sum stays dead
                if size[s]:
                    refuse(kept[begin[s]])
        if c in (PS_STORE_RESET, PS_SWAP):
            parked[(ln, q)] = last.get(ln, -1)
        last[ln] = s

    # each row's path of segments, root first
    idx = np.arange(fin.sum())
    cur = kseg[fin][np.argsort(kept[fin])]     # FINAL segments, FINAL order
    row_of, seg_of, depth = [idx], [cur], [np.zeros(len(cur), np.int64)]
    while (up := parent[cur] >= 0).any():
        idx, cur = idx[up], parent[cur[up]]
        row_of.append(idx)
        seg_of.append(cur)
        depth.append(np.full(len(cur), len(depth)))
    path = np.concatenate(seg_of)[np.lexsort((-np.concatenate(depth),
                                              np.concatenate(row_of)))]
    n = size[path]
    pos = np.repeat(begin[path] - (np.cumsum(n) - n), n) + np.arange(n.sum())
    return t[kept[pos]], lane[kept[pos]]


def _row_words(prog: Program):
    """``(t, lane, src, fin)`` of the row-serial entries in FINAL order
    (`_row_chains`): each entry's word, the x row it addresses and whether
    it is a FINAL.  Raises ``ValueError`` on a row outside the resident x
    buffer or a row finalised twice."""
    t, lane = _row_chains(prog)
    op, src, _, _ = (f[:, 0] for f in decode_instructions(
        prog.instr[t, :, lane][..., None], prog.planes))
    src = src.astype(np.int64)
    fin = op == OP_FINAL
    spare = _resident_rows(prog) - 1
    bad = np.flatnonzero((src < 0) | (src >= spare))
    if len(bad):
        i = bad[0]
        raise ValueError(
            f"word at cycle {t[i]}, lane {lane[i]} addresses x row {src[i]}, "
            f"outside the {spare} rows its x buffer holds")
    rows, first = np.unique(src[fin], return_index=True)
    if len(rows) < fin.sum():
        twice = np.setdiff1d(np.arange(fin.sum()), first)[0]
        raise ValueError(f"x row {src[fin][twice]} is finalised twice")
    return t, lane, src, fin


def _check_reads(t, lane, src, fin, rows: int) -> None:
    """Refuse an EDGE that reads an x row whose FINAL does not come earlier
    in the entries' order (``rows`` bounds the row numbers)."""
    m = len(src)
    done = np.full(rows, m, np.int64)      # stream position of each FINAL
    done[src[fin]] = np.flatnonzero(fin)
    early = np.flatnonzero(~fin & (done[src] > np.arange(m)))
    if len(early):
        i = early[0]
        raise ValueError(
            f"EDGE at cycle {t[i]}, lane {lane[i]} reads x row {src[i]}, "
            f"whose FINAL does not come before it")


def _pack(prog: Program, t, lane, src, dst, spare: int) -> Stream:
    """The entries' staged rows ``src``/``dst`` and pre-gathered values, cut
    into blocks of `_block_len` entries, only the last one short and padded
    with the filler entry (the spare row as source and destination, value
    0: a FINAL of the spare row, which stores only there and leaves a zero
    sum)."""
    m = len(src)
    k = _block_len(m)
    g = max(-(-m // k), 1)
    instr = np.empty((ROW_PLANES, g * k), np.int32)
    instr[:, m:] = spare
    instr[SRC, :m] = src
    instr[DST, :m] = dst
    vals = np.zeros(g * k, np.float32)
    vals[:m] = prog.stream[prog.val_idx[t, lane]]
    counts = np.full(g, k, np.int32)
    counts[-1] = m - (g - 1) * k
    return Stream(
        instr=instr.reshape(ROW_PLANES, g, k).transpose(1, 0, 2).reshape(-1),
        values=vals, counts=counts, k=k,
        slot_words=prog.cycles * prog.num_cus)


def _stage_rows(prog: Program) -> Stream:
    """Stage the resident kernel's row-serial stream (`kernel` docstring).

    The words of `_row_chains` in FINAL order, each decoded into its
    `kernel.ROW_PLANES` planes (`kernel.SRC`, `kernel.DST`: a FINAL stores
    to its own row, an EDGE to the x buffer's spare top row) and its
    pre-gathered value (`_pack`).  Raises ``ValueError`` on a row outside
    the x buffer, a row finalised twice, or an EDGE reading a
    row whose FINAL does not come earlier in this order.
    """
    t, lane, src, fin = _row_words(prog)
    spare = _resident_rows(prog) - 1
    _check_reads(t, lane, src, fin, spare)
    return _pack(prog, t, lane, src, np.where(fin, src, spare), spare)


def _blocked_order(prog: Program):
    """``(t, lane, src, fin)`` of the blocked kernel's entries: the rows of
    `_row_words` in ascending row order, each still its EDGE words in
    stream order and then its FINAL.  A row's sources are smaller rows, so
    in a level-ordered program this is level order, and a row's sources sit
    at most about a level's width back.  Raises ``ValueError`` as
    `_row_words` does, and on an EDGE reading a row whose FINAL does not
    come earlier in this order."""
    t, lane, src, fin = _row_words(prog)
    if len(src):
        ends = np.flatnonzero(fin)
        row = src[ends][np.searchsorted(ends, np.arange(len(src)))]
        order = np.argsort(row, kind="stable")
        t, lane, src, fin = t[order], lane[order], src[order], fin[order]
    _check_reads(t, lane, src, fin, _resident_rows(prog) - 1)
    return t, lane, src, fin


def _stage_blocked(prog: Program, plan: WindowPlan) -> Stream:
    """Stage the blocked kernel's row-serial stream for ``plan``.

    The entries of `_blocked_order`, in blocks of `_block_len` entries as
    `plan_window` cut them.  Each entry's x rows are staged into the x ref
    of `kernel.sptrsv_pallas_blocked`: row ``r`` at ring row ``r %
    kernel.ring_rows(window, stride)``, and an EDGE stores to the spare row
    past the ring.  Raises ``ValueError`` on a row outside its block's
    window, and as `_blocked_order` does.
    """
    t, lane, src, fin = _blocked_order(prog)
    m, w = len(src), plan.window
    k = _block_len(m)
    if -(-m // k) != plan.num_blocks:
        raise ValueError(f"plan of {plan.num_blocks} blocks for a stream of "
                         f"{-(-m // k)}")
    blk = np.arange(m) // k
    row = src - blk * plan.stride
    bad = np.flatnonzero((row < 0) | (row >= w))
    if len(bad):
        i = bad[0]
        lo = blk[i] * plan.stride
        raise ValueError(
            f"word at cycle {t[i]}, lane {lane[i]} addresses x row {src[i]}, "
            f"outside its block {blk[i]}'s window of rows [{lo}, {lo + w})")
    ring = ring_rows(w, plan.stride)
    row = src % ring
    return _pack(prog, t, lane, row, np.where(fin, row, ring), ring)


def instr_buffer_bytes(prog: Program) -> int:
    """SMEM bytes of a kernel's double-buffered instruction streaming.

    Two segment buffers of K entries, each entry its `kernel.ROW_PLANES`
    int32 planes and its pre-gathered f32 value; K is at most
    `kernel.ROW_BLOCK` (192 KiB), the same for both kernels, which run the
    same entries.  A TPU v5e core has 1 MiB of SMEM.
    """
    return 2 * _block_len(len(_row_chains(prog)[0])) * (4 * ROW_PLANES + 4)


def state_bytes(prog: Program, nb: int, *, placement: str,
                plan: WindowPlan | None = None) -> dict:
    """Memory accounting of one Pallas solve, in tiled bytes.

    Returns ``{"xb", "vmem", "instr"}`` for ``nb`` RHS columns under
    ``placement`` (``"blocked"`` needs the `WindowPlan`): ``xb`` is the
    solve state (resident: the one x buffer b is copied into; blocked: the
    x ring and its spare row, b read from HBM), ``vmem`` all of the
    kernel's VMEM (the solve state alone: neither kernel has lane state),
    ``instr`` the SMEM instruction buffers.
    """
    if placement == "blocked":
        if plan is None or not plan.feasible:
            raise ValueError("blocked accounting needs a feasible WindowPlan")
        xb = plan.state_bytes(nb)
    elif placement == "resident":
        xb = resident_state_bytes(_resident_rows(prog), nb)
    else:
        raise ValueError(f"unknown placement {placement!r}")
    return {"xb": xb, "vmem": xb, "instr": instr_buffer_bytes(prog)}


def build_solver_cols(
    prog: Program,
    width: int,
    *,
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
    ``closure.plan``, the resolved interpreter flag as
    ``closure.interpret``, and the compaction as ``closure.stream_words``
    (entries executed per solve) against ``closure.slot_words`` (the
    ``T * P`` lane slots of the uncompacted grid), for tests and
    diagnostics.

    A compiled resident solve whose x buffer, with Mosaic's headroom,
    cannot fit a core's VMEM (`kernels.common.resident_fits`) raises
    `PlacementInfeasibleError` naming its rows and bytes, before Mosaic is
    asked for it; the interpreter, which has no VMEM, runs it.
    """
    mode, plan = resolve_placement(
        prog, width, placement=placement, vmem_limit_bytes=vmem_limit_bytes,
        x_block_rows=x_block_rows,
    )
    interpret = resolve_interpret(interpret)
    if mode == "resident" and not interpret and \
            not resident_fits(prog.n, width):
        rows = _resident_rows(prog)
        vmem = resident_state_bytes(rows, width)
        raise PlacementInfeasibleError(
            f"resident x buffer of {rows} rows needs {vmem} bytes of VMEM "
            f"and {VMEM_HEADROOM} of headroom, over the {VMEM_CAPACITY} a "
            f"core has (placement={placement!r})",
            detail={"rows": rows, "vmem_bytes": vmem,
                    "vmem_headroom": VMEM_HEADROOM,
                    "vmem_capacity": VMEM_CAPACITY})
    if mode == "resident":
        stream = _stage_rows(prog)
        n_rows = _resident_rows(prog)
    else:
        stream = _stage_blocked(prog, plan)
        n_rows = plan.n_hbm
    instr = jnp.asarray(stream.instr)
    values = jnp.asarray(stream.values)
    counts = jnp.asarray(stream.counts)
    n = prog.n

    def solve_cols(bmat: jnp.ndarray) -> jnp.ndarray:
        bp = jnp.zeros((n_rows, width), jnp.float32)
        bp = bp.at[:n].set(jnp.asarray(bmat, jnp.float32))
        if mode == "resident":
            x = sptrsv_pallas(instr, values, counts, bp, interpret=interpret)
        else:
            x = sptrsv_pallas_blocked(
                instr, values, counts, bp, window=plan.window,
                stride=plan.stride, interpret=interpret)
        return x[:n]

    # one jit folds the row permutation, the pad and the slice into the
    # kernel dispatch
    solve_cols = jax.jit(permute_rows(prog, solve_cols))
    solve_cols.placement = mode
    solve_cols.plan = plan
    solve_cols.interpret = interpret
    solve_cols.stream_words = stream.stream_words
    solve_cols.slot_words = stream.slot_words
    return solve_cols


def solve(
    prog: Program,
    b: np.ndarray,
    *,
    interpret: bool | None = None,
    placement: str = "auto",
    vmem_limit_bytes: int | None = None,
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
        prog, batch=bmat.shape[1], placement=placement, vmem_limit_bytes=vmem_limit_bytes,
        x_block_rows=x_block_rows, interpret=interpret,
    )
    x = np.asarray(solver(bmat))
    return x[:, 0] if single else x
