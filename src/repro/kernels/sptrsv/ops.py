"""Compiler-side wrapper: run a compiled `Program` through the Pallas kernel.

Two memory placements for the solve state (DESIGN.md §1):

  * ``resident`` — x lives in VMEM for the whole solve, in one buffer that
    b is copied into (`kernel.sptrsv_pallas`); fastest while it fits.
  * ``blocked``  — x and b stay in HBM and the kernel slides a row-blocked
    VMEM window over them (`kernel.sptrsv_pallas_blocked`), flushing and
    shifting it at cycle-block boundaries with async DMA.  This is the
    large-n path: VMEM use is bounded by the window, not by n.

``placement="auto"`` (the default) picks per solve: resident while the
resident x footprint is under ``vmem_limit_bytes``, blocked beyond it
whenever the program's row-access envelope admits a sliding window
(`plan_window`) whose x and b windows take less.
Footprints are counted as the chip lays them out: a ``[rows, B]`` f32 array
takes whole (8, 128) tiles (`kernel.tiled_bytes`), so at B=16 it occupies
8x its logical bytes.

The wrapper performs the compiler-side data staging the hardware's stream
memory provides: values are pre-gathered per instruction word so the kernel
streams them sequentially (no positional indirection, as in the paper's
stream-memory design), and the compiler's packed instruction words
(``Program.instr``, ``[T, planes, P]`` int32 — DESIGN.md §Perf,
"Instruction encoding") are decoded once into the rows each access uses
(`kernel`'s module docstring) and flattened, so each block arrives in SMEM
with a single DMA per stream.  The resident kernel gets the row-serial
stream (`_stage_rows`): the rows in the order of their FINALs, in blocks of
`kernel.ROW_BLOCK` entries of ``4 * ROW_PLANES + 4`` bytes.  The blocked
kernel gets the lane-state stream (`_stage_instructions`): per cycle block
the words that do something, padded to one segment length K, in entries of
``4 * STREAM_PLANES + 4`` bytes.  No-op lane slots are neither streamed nor
executed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.errors import PlacementInfeasibleError
from repro.core.executor import _psum_slots, as_batch
from repro.core.program import (
    OP_EDGE,
    OP_FINAL,
    OP_NOP,
    PS_KEEP,
    PS_LOAD,
    PS_RESET,
    PS_STORE_RESET,
    PS_SWAP,
    Program,
    decode_instructions,
)

from repro.kernels.common import resolve_interpret

from .kernel import (
    DST,
    EDGE,
    FB,
    PARK,
    PV_SRC,
    ROW,
    ROW_BLOCK,
    ROW_PLANES,
    SEGMENT_ALIGN,
    SRC,
    STREAM_PLANES,
    UNROLL,
    X_DST,
    blocked_state_bytes,
    lane_rows,
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
    "Stream",
    "WindowPlan",
    "DEFAULT_STATE_BYTES",
]

# auto-placement threshold for the tiled VMEM solve-state footprint.
# A TPU v5e kernel gets 16 MiB of scoped VMEM by default, shared with the
# feedback/psum lane state and Mosaic's own scratch; 4 MiB of solve state
# keeps the resident kernel inside it.  Overridable per call
# (``vmem_limit_bytes``).
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
    the worst block plus one spare top row, which takes the x stores of
    words that finalize nothing (`kernel`'s ``X_DST``) — both rounded to
    the f32 sublane granularity.

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
    window = max(w_req + 1, 2 * stride, min_window or 0, 2 * _ROW_ALIGN)
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
    x footprint for ``nb`` RHS columns against ``vmem_limit_bytes``
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
    resident_bytes = tiled_bytes(_resident_rows(prog), nb)
    if resident_bytes <= vmem_limit_bytes or not plan.feasible:
        return "resident", None
    if plan.state_bytes(nb) >= resident_bytes:
        return "resident", None  # window as big as the vector: no point
    return "blocked", plan


def _resident_rows(prog: Program) -> int:
    """Rows of the resident x buffer: n and at least one spare row on top,
    which takes the x stores of words that finalize nothing."""
    return _round_up(prog.n + 1, _ROW_ALIGN)


def _pad_to(arr: np.ndarray, t_pad: int, fill=0) -> np.ndarray:
    t = arr.shape[0]
    if t == t_pad:
        return arr
    out = np.full((t_pad,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[:t] = arr
    return out


@dataclasses.dataclass(frozen=True)
class Stream:
    """A program staged for a kernel: its entries, compacted, in blocks.

    ``instr`` is ``[G, planes, k]`` flattened, ``values`` ``[G, k]``
    flattened, ``counts[g]`` the entries of block g; ``k`` is a
    `kernel.SEGMENT_ALIGN` multiple and each block is padded with a filler
    entry.  Lane-state streams (`_stage_instructions`): block g holds the
    active words of ``cycles_per_block`` cycles in the planes `kernel.ROW`
    .. `kernel.EDGE`.  Row-serial streams (`_stage_rows`): blocks of ``k``
    entries, only the last one short, in the planes `kernel.SRC` and
    `kernel.DST`.
    """

    instr: np.ndarray
    values: np.ndarray
    counts: np.ndarray
    k: int
    slot_words: int  # T_pad * P: the lane slots of the uncompacted grid

    @property
    def stream_words(self) -> int:
        """Entries the kernel executes per solve (counts rounded up to the
        unroll factor, filler included)."""
        return int((-(-self.counts // UNROLL) * UNROLL).sum())


def _active_blocks(prog: Program, cycles_per_block: int):
    """``(words [G, planes, tb*P], active [G, tb*P])`` of the padded program,
    each block's lane slots in cycle-major, lane-minor order."""
    tb, p, planes = cycles_per_block, prog.num_cus, prog.planes
    g = -(-prog.cycles // tb)
    words = _pad_to(prog.instr, g * tb)                  # [T_pad, planes, P]
    op, _, ctl, _ = decode_instructions(words, planes)   # [T_pad, P]
    active = ((op != OP_NOP) | (ctl != PS_KEEP)).reshape(g, tb * p)
    words = words.reshape(g, tb, planes, p).transpose(0, 2, 1, 3)
    return words.reshape(g, planes, tb * p), active


def _segment_len(active: np.ndarray) -> int:
    return _round_up(max(int(active.sum(axis=1).max()), 1), SEGMENT_ALIGN)


def _planes(op, row, ctl, slot, lane, p: int, num_slots: int,
            spare: int) -> np.ndarray:
    """``[STREAM_PLANES, m]`` staged planes of decoded words (see `kernel`);
    ``row`` is each word's x row in the ref the kernel addresses, ``spare``
    that ref's top row.

    Lane-state rows: feedback of lane l at l, psum slot j of lane l at ``p +
    l * num_slots + j`` (overflow slots share the last), then the zero row
    and the trash row (`kernel.lane_rows`).
    """
    zero = lane_rows(p, num_slots) - 2
    trash = zero + 1
    slot_row = p + lane * num_slots + np.minimum(slot, num_slots - 1)
    out = np.empty((STREAM_PLANES, len(op)), np.int64)
    out[ROW] = np.where(op == OP_NOP, 0, row)
    out[PV_SRC] = np.where((ctl == PS_LOAD) | (ctl == PS_SWAP), slot_row,
                           np.where((ctl == PS_RESET) | (ctl == PS_STORE_RESET),
                                    zero, lane))
    out[FB] = lane
    out[PARK] = np.where((ctl == PS_STORE_RESET) | (ctl == PS_SWAP),
                         slot_row, trash)
    out[X_DST] = np.where(op == OP_FINAL, row, spare)
    out[EDGE] = op == OP_EDGE
    return out


def _filler(p: int, num_slots: int, spare: int) -> np.ndarray:
    """The filler entry's planes: it sums from the zero row and stores to
    the trash row and the spare x row only, so it changes no row that is
    read."""
    zero = lane_rows(p, num_slots) - 2
    out = np.zeros(STREAM_PLANES, np.int64)
    out[[PV_SRC, FB, PARK, X_DST]] = [zero, zero + 1, zero + 1, spare]
    return out


def _stage_instructions(prog: Program, cycles_per_block: int,
                        plan: WindowPlan | None = None) -> Stream:
    """Stage the blocked kernel's lane-state stream: compact the packed
    instruction words and pre-gather the values.

    The program already carries the packed ``[T, planes, P]`` words — the
    pack happens once at compile time; staging drops the no-op lane slots
    of each cycle block (pad cycles are all no-op), decodes every word it
    keeps into the rows the kernel's accesses use (`_planes`), gathers the
    f32 values per kept word so the kernel streams them positionally, and
    pads each block's segment to the common length (see `Stream`).  x rows
    index the resident x buffer, or with a blocked ``plan`` the window of
    their block; a row outside it raises ``ValueError``.
    """
    tb, p, planes = cycles_per_block, prog.num_cus, prog.planes
    words, active = _active_blocks(prog, tb)
    g = active.shape[0]
    values = _pad_to(prog.stream[prog.val_idx].astype(np.float32), g * tb)
    values = values.reshape(g, tb * p)
    k = _segment_len(active)
    slots = _psum_slots(prog)

    blk, slot = np.nonzero(active)               # row-major: in block order
    pos = (np.cumsum(active, axis=1) - 1)[blk, slot]
    kept = words[blk, :, slot][..., None]        # [m, planes, 1]
    op, src, ctl, ps = (f[:, 0] for f in decode_instructions(kept, planes))
    row = src.astype(np.int64)
    if plan is None:
        spare = _resident_rows(prog) - 1
    else:
        spare = plan.window - 1
        row = row - blk * plan.stride
    bad = (op != OP_NOP) & ((row < 0) | (row >= spare))
    if bad.any():
        i = int(np.flatnonzero(bad)[0])
        raise ValueError(
            f"word of cycle block {blk[i]} addresses x row {src[i]}, outside "
            f"the {spare} rows its {'window' if plan else 'x buffer'} holds")
    instr = np.empty((g, STREAM_PLANES, k), np.int32)
    instr[...] = _filler(p, slots, spare)[:, None]
    instr[blk, :, pos] = _planes(op, row, ctl, ps, slot % p, p, slots, spare).T
    vals = np.zeros((g, k), np.float32)
    vals[blk, pos] = values[blk, slot]
    return Stream(instr=instr.reshape(-1), values=vals.reshape(-1),
                  counts=active.sum(axis=1).astype(np.int32), k=k,
                  slot_words=g * tb * p)


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


def _stage_rows(prog: Program, cycles_per_block: int = 128) -> Stream:
    """Stage the resident kernel's row-serial stream (`kernel` docstring).

    The words of `_row_chains`, each decoded into its `kernel.ROW_PLANES`
    planes (`kernel.SRC`, `kernel.DST`) and its pre-gathered value, cut
    into blocks of ``k`` entries, only the last one short and padded with
    the filler entry (the spare row as source and destination, value 0: a
    FINAL of the spare row, which stores only there and leaves a zero
    sum).
    ``cycles_per_block`` counts the uncompacted lane slots (``slot_words``)
    only.  Raises ``ValueError`` on a row outside the x buffer, a row
    finalised twice, or an EDGE reading a row whose FINAL does not come
    earlier in this order.
    """
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
    m = len(src)
    done = np.full(spare, m, np.int64)     # stream position of each FINAL
    rows, first = np.unique(src[fin], return_index=True)
    if len(rows) < fin.sum():
        twice = np.setdiff1d(np.arange(fin.sum()), first)[0]
        raise ValueError(f"x row {src[fin][twice]} is finalised twice")
    done[rows] = np.flatnonzero(fin)[first]
    early = np.flatnonzero(~fin & (done[src] > np.arange(m)))
    if len(early):
        i = early[0]
        raise ValueError(
            f"EDGE at cycle {t[i]}, lane {lane[i]} reads x row {src[i]}, "
            f"whose FINAL does not come before it")

    k = min(ROW_BLOCK, _round_up(max(m, 1), SEGMENT_ALIGN))
    g = max(-(-m // k), 1)
    instr = np.empty((ROW_PLANES, g * k), np.int32)
    instr[:, m:] = spare
    instr[SRC, :m] = src
    instr[DST, :m] = np.where(fin, src, spare)
    vals = np.zeros(g * k, np.float32)
    vals[:m] = prog.stream[prog.val_idx[t, lane]]
    counts = np.full(g, k, np.int32)
    counts[-1] = m - (g - 1) * k
    return Stream(
        instr=instr.reshape(ROW_PLANES, g, k).transpose(1, 0, 2).reshape(-1),
        values=vals, counts=counts, k=k,
        slot_words=_round_up(prog.cycles, cycles_per_block) * prog.num_cus)


def instr_buffer_bytes(prog: Program, cycles_per_block: int = 128,
                       placement: str = "resident") -> int:
    """SMEM bytes of a kernel's double-buffered instruction streaming.

    Two segment buffers of K entries, each entry its int32 planes and its
    pre-gathered f32 value.  The resident kernel's row-serial stream has
    `kernel.ROW_PLANES` planes and K of at most `kernel.ROW_BLOCK` entries
    (192 KiB).  The blocked kernel's lane-state stream has
    `kernel.STREAM_PLANES` planes and K the largest cycle block's count of
    active words rounded up to `kernel.SEGMENT_ALIGN` (1024): at worst
    (every lane active in every cycle) ``cycles_per_block * P``, 448 KiB at
    128 cycles of 64 lanes.  A TPU v5e core has 1 MiB of SMEM.
    """
    if placement == "resident":
        return 2 * _stage_rows(prog, cycles_per_block).k * (4 * ROW_PLANES + 4)
    if placement != "blocked":
        raise ValueError(f"unknown placement {placement!r}")
    _, active = _active_blocks(prog, cycles_per_block)
    return 2 * _segment_len(active) * (4 * STREAM_PLANES + 4)


def state_bytes(prog: Program, nb: int, *, placement: str,
                plan: WindowPlan | None = None,
                cycles_per_block: int = 128) -> dict:
    """Memory accounting of one Pallas solve, in tiled bytes.

    Returns ``{"xb", "vmem", "instr"}`` for ``nb`` RHS columns under
    ``placement`` (``"blocked"`` needs the `WindowPlan`): ``xb`` is the
    solve state (resident: the one x buffer b is copied into; blocked: the
    x and b windows), ``vmem`` all of the kernel's VMEM (the solve state,
    plus the blocked kernel's feedback/psum lane state; the resident kernel
    has none), ``instr`` the SMEM instruction buffers.
    """
    if placement == "blocked":
        if plan is None or not plan.feasible:
            raise ValueError("blocked accounting needs a feasible WindowPlan")
        xb = plan.state_bytes(nb)
        vmem = blocked_state_bytes(plan.window, nb, prog.num_cus,
                                   _psum_slots(prog))
    elif placement == "resident":
        xb = vmem = resident_state_bytes(_resident_rows(prog), nb)
    else:
        raise ValueError(f"unknown placement {placement!r}")
    return {"xb": xb, "vmem": vmem,
            "instr": instr_buffer_bytes(prog, cycles_per_block, placement)}


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
    ``closure.plan``, the resolved interpreter flag as
    ``closure.interpret``, and the compaction as ``closure.stream_words``
    (entries executed per solve) against ``closure.slot_words`` (the
    ``T_pad * P`` lane slots of the uncompacted grid), for tests and
    diagnostics.
    """
    mode, plan = resolve_placement(
        prog, width, placement=placement, vmem_limit_bytes=vmem_limit_bytes,
        cycles_per_block=cycles_per_block, x_block_rows=x_block_rows,
    )
    if mode == "resident":
        stream = _stage_rows(prog, cycles_per_block)
        n_rows = _resident_rows(prog)
    else:
        stream = _stage_instructions(prog, cycles_per_block, plan)
        n_rows = plan.n_hbm
    instr = jnp.asarray(stream.instr)
    values = jnp.asarray(stream.values)
    counts = jnp.asarray(stream.counts)
    n = prog.n
    interpret = resolve_interpret(interpret)

    @jax.jit  # fold the pad/slice into the kernel dispatch
    def solve_cols(bmat: jnp.ndarray) -> jnp.ndarray:
        bp = jnp.zeros((n_rows, width), jnp.float32)
        bp = bp.at[:n].set(jnp.asarray(bmat, jnp.float32))
        if mode == "resident":
            x = sptrsv_pallas(instr, values, counts, bp, interpret=interpret)
        else:
            x = sptrsv_pallas_blocked(
                instr, values, counts, bp, num_cus=prog.num_cus,
                num_slots=_psum_slots(prog), window=plan.window,
                stride=plan.stride, interpret=interpret)
        return x[:n]

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
