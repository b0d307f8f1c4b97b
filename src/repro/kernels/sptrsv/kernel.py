"""Pallas TPU kernel executing a compiled SpTRSV VLIW instruction stream.

TPU adaptation of the paper's accelerator (DESIGN.md §1):
  * the instruction stream stays in HBM (`pl.ANY`) and is streamed into
    SMEM in cycle blocks by explicit async DMA ("data in the instruction
    memory ... is accessed sequentially", §III-B);
  * stream-memory values are pre-gathered per instruction word by the
    compiler wrapper (ops.py), so the kernel reads them sequentially too;
  * the solution vector, the feedback registers and the psum register file
    live in VMEM refs (the software-managed scratchpads of the paper).

Per-entry execution.  The kernel runs the lanes of a VLIW cycle one after
another on the scalar unit, so on the TPU a program is simply its *active*
lane-words in cycle-major, lane-minor order; the cycle x lane grid is a
hardware notion the kernel does not need.  The wrapper
(`ops._stage_instructions`) keeps, per cycle block, only the words that do
something (op not NOP, or psum control not KEEP), pads every block's
segment to one common length K, and stages each entry pre-decoded: its
pre-gathered value and `STREAM_PLANES` int32 planes of row indices and one
flag (`ROW` .. `EDGE` below).  A per-block count table in SMEM says how
many entries block g holds; the kernel runs ``ceil(count_g / UNROLL)``
iterations of a loop whose body executes `UNROLL` entries.

Address-selected rows.  The scalar unit sets the pace: one word costs the
same at B=1 and B=16, and decoding the packed word and turning its fields
into select masks took most of the scalar work of an entry.  So the
wrapper resolves every choice the psum control and the op make into the
row an access uses, and the kernel only loads, computes and stores:

  * the lane state is one VMEM ref ``ls`` of `lane_rows` rows: the
    feedback rows (one per lane), the psum register file (``num_slots``
    rows per lane), a zero row and a trash row;
  * the running sum starts from the row ``PV_SRC`` names: the lane's
    feedback row (KEEP), the zero row (RESET, STORE_RESET) or a psum slot
    (LOAD, SWAP);
  * feedback is parked into the row ``PARK`` names: the psum slot on
    STORE_RESET/SWAP, else the trash row;
  * ``(b[src] - psum) * v`` is stored to the x row ``X_DST`` names: the
    FINAL's own row, else the x ref's top row, which no word loads
    (`ops._resident_rows` and `ops.plan_window` reserve it);
  * the one select left is the MAC: ``psum + v * x[src]`` where ``EDGE``
    is set.

Per lane the arithmetic and its order are those of the `lax.scan`
executor, so the results are bit-identical to it; the filler entry (the
zero row as its source, the trash rows as its destinations) changes no row
that is read.  The loop body is one basic block, so the scheduler can
overlap one entry's SMEM reads with the previous entry's row work.

Every access is a dynamically indexed row of a VMEM ref, which Mosaic
lowers directly; no vector gather/scatter by 64 independent indices is
needed.  Lanes of one cycle are independent (the scheduler guarantees an
EDGE only reads x rows finalized in earlier cycles and FINAL rows are
distinct), so running them one after another is exactly the synchronous
cycle semantics of the numpy oracle, and dropping the no-op words changes
nothing but the work.

Double-buffered cycle-block streaming: the kernel owns two SMEM stream
buffers and, while executing cycle block g out of one buffer, prefetches
block g+1 into the other (`pltpu.make_async_copy` + per-slot DMA
semaphores), so instruction HBM traffic overlaps compute.

Multi-RHS batching: every row carries a trailing batch axis (``x[n_pad,
B]``, ``ls[lane_rows, B]``), so one pass over the instruction stream
solves B right-hand sides.

Two memory-placement regimes for the solve state (DESIGN.md §1):

  * `sptrsv_pallas` — x fully VMEM-resident, in one ``[n_pad, B]`` buffer
    that starts as b: b arrives in HBM and one DMA copies it into the x
    buffer.  Row i's b is read only by row i's FINAL, before x[i] is
    written, and no EDGE reads row i before that FINAL (the schedule's
    guarantee, checked by `core/analysis/hazards.py`), so FINAL reads b[i]
    from the x row it has just loaded.  Fastest while ``x[n_pad, B]`` fits.
  * `sptrsv_pallas_blocked` — x and b stay HBM-resident (`pl.ANY`); the
    kernel owns a row-blocked VMEM *window* of `window` solution rows that
    slides forward by a fixed `stride` rows per cycle block.  At each block
    boundary the `stride` rows that leave the window are flushed to HBM
    (they are final — the schedule metadata proves no later block touches
    them) and the shared `window - stride` rows are carried across by a
    VMEM-to-VMEM copy.  The rows entering the window need no refill: no
    earlier block touched them, and the schedule writes each row (FINAL)
    before any lane reads it.

The feasibility conditions (every block's touched-row envelope inside its
window; see `ops.plan_window`) are checked by the wrapper against the
compiler-emitted per-cycle row ranges (`Program.row_lo/row_hi`).  The
wrapper stages every x row relative to the ref it addresses (the blocked
kernel's rows relative to their block's window) and checks it lies in that
ref, so a corrupt program cannot address VMEM outside the solve state; the
kernel uses the staged rows as they are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

__all__ = [
    "SEGMENT_ALIGN",
    "STREAM_PLANES",
    "UNROLL",
    "sptrsv_pallas",
    "sptrsv_pallas_blocked",
    "blocked_state_bytes",
    "lane_rows",
    "resident_state_bytes",
    "tiled_bytes",
    "vmem_limit",
]

# Mosaic lays a 2-D f32 VMEM array out in (8, 128) tiles.
_SUBLANES, _LANES = 8, 128
# Default scoped-VMEM limit of a TPU v5e kernel; a kernel whose accounted
# state needs more raises its own limit (`vmem_limit`).
_SCOPED_VMEM_DEFAULT = 16 << 20
_VMEM_HEADROOM = 4 << 20  # Mosaic's own internal scratch
# Entries executed per iteration of the kernel's stream loop.
UNROLL = 8
# Segment length granularity: Mosaic slices a 1-D HBM array only in whole
# (1024,) tiles, and a multiple of it is a multiple of UNROLL.
SEGMENT_ALIGN = 1024
# The int32 planes of a staged entry: the x row it loads, the lane-state rows
# its running sum starts from, its feedback lives in and feedback is parked
# in, the x row it stores, and whether it is an EDGE (see the module
# docstring).
ROW, PV_SRC, FB, PARK, X_DST, EDGE = range(6)
STREAM_PLANES = 6


def tiled_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of a ``[rows, cols]`` f32 array in (8, 128) tiles."""
    return (-(-rows // _SUBLANES) * _SUBLANES) * (-(-cols // _LANES) * _LANES) * 4


def vmem_limit(state_bytes: int) -> int:
    """Scoped-VMEM limit for a kernel whose solve state takes ``state_bytes``."""
    return max(_SCOPED_VMEM_DEFAULT, state_bytes + _VMEM_HEADROOM)


def lane_rows(p: int, num_slots: int) -> int:
    """Rows of the lane state: ``p`` feedback rows, ``p * num_slots`` psum
    slot rows, the zero row and the trash row, in that order."""
    return p * (num_slots + 1) + 2


def _lane_state_bytes(p: int, num_slots: int, nb: int) -> int:
    """VMEM bytes of the lane state (`lane_rows`)."""
    return tiled_bytes(lane_rows(p, num_slots), nb)


def resident_state_bytes(n_pad: int, nb: int, p: int, num_slots: int) -> int:
    """VMEM bytes of the resident kernel: the x buffer (b in place) + lane
    state."""
    return tiled_bytes(n_pad, nb) + _lane_state_bytes(p, num_slots, nb)


def blocked_state_bytes(window: int, nb: int, p: int, num_slots: int) -> int:
    """VMEM bytes of the blocked kernel: two x and two b windows + lane state."""
    return 4 * tiled_bytes(window, nb) + _lane_state_bytes(p, num_slots, nb)


def _run_block(ibuf, vbuf, slot, count, x_ref, b_ref, ls_ref, *, k):
    """Execute the ``count`` entries of the cycle block in buffer ``slot``.

    ``x_ref``/``b_ref`` hold the solution/RHS rows the block's staged rows
    index (the whole padded vector in the VMEM-resident kernel, the block's
    window in the blocked one); the top one is spare.  ``b_ref=None`` means
    b is in ``x_ref`` (the resident kernel): FINAL reads it from the row it
    loads.  ``ls_ref`` is the lane state (`lane_rows`).  ``ibuf`` is
    the flat SMEM buffer ``[2 * STREAM_PLANES * k]`` of staged planes,
    ``vbuf`` the flat SMEM value buffer ``[2 * k]``.  Entries past ``count``
    up to the next `UNROLL` multiple are filler.
    """

    def entry(e):
        def plane(j):
            return ibuf[(slot * STREAM_PLANES + j) * k + e]

        # the psum control mux (S1/S2 of Fig. 4b) is resolved into PV_SRC
        # and PARK by staging
        row, fb_row = plane(ROW), plane(FB)
        v = vbuf[slot * k + e]
        x_row = x_ref[pl.ds(row, 1), :]
        b_row = x_row if b_ref is None else b_ref[pl.ds(row, 1), :]
        pv = ls_ref[pl.ds(plane(PV_SRC), 1), :]
        fb = ls_ref[pl.ds(fb_row, 1), :]
        ls_ref[pl.ds(plane(PARK), 1), :] = fb
        pv = jnp.where(plane(EDGE) != 0, pv + v * x_row, pv)
        x_ref[pl.ds(plane(X_DST), 1), :] = (b_row - pv) * v
        ls_ref[pl.ds(fb_row, 1), :] = pv

    def step(i, carry):
        for u in range(UNROLL):
            entry(i * UNROLL + u)
        return carry

    jax.lax.fori_loop(0, (count + UNROLL - 1) // UNROLL, step, 0)


def _stream_dmas(instr_ref, val_ref, ibuf, vbuf, isem, vsem, *, k):
    """(instr_dma, val_dma) constructors for cycle block g into buffer slot."""
    wblk = STREAM_PLANES * k

    def instr_dma(slot, g):
        return pltpu.make_async_copy(
            instr_ref.at[pl.ds(g * wblk, wblk)], ibuf.at[pl.ds(slot * wblk, wblk)],
            isem.at[slot])

    def val_dma(slot, g):
        return pltpu.make_async_copy(
            val_ref.at[pl.ds(g * k, k)], vbuf.at[pl.ds(slot * k, k)],
            vsem.at[slot])

    return instr_dma, val_dma


def _stream_scratch(k, p, num_slots, nb):
    """Scratch shared by both kernels: SMEM stream buffers + lane state."""
    return [
        pltpu.SMEM((2 * STREAM_PLANES * k,), jnp.int32),    # ibuf
        pltpu.SMEM((2 * k,), jnp.float32),                  # vbuf
        pltpu.VMEM((lane_rows(p, num_slots), nb), jnp.float32),  # ls
        pltpu.SemaphoreType.DMA((2,)),                      # isem
        pltpu.SemaphoreType.DMA((2,)),                      # vsem
    ]


def _stream_shape(instr, values, counts):
    """(num_blocks, k) of a staged stream; checks the three agree."""
    num_blocks = counts.shape[0]
    k = values.shape[0] // num_blocks
    assert values.shape[0] == num_blocks * k and k % SEGMENT_ALIGN == 0, \
        "pad every block's segment to one SEGMENT_ALIGN multiple first"
    assert instr.shape[0] == num_blocks * STREAM_PLANES * k, \
        "instr/values stream mismatch"
    return num_blocks, k


def _kernel(
    # inputs
    instr_ref,  # [G * STREAM_PLANES * K] int32, HBM (streamed by DMA)
    val_ref,    # [G * K]                f32,   HBM (pre-gathered values)
    cnt_ref,    # [G]                    int32, SMEM (entries per block)
    b_hbm_ref,  # [n_pad, B]             f32,   HBM — copied into x once
    # outputs
    x_ref,      # [n_pad, B]             f32,   VMEM (starts as b)
    # scratch
    ibuf, vbuf, ls_ref, isem, vsem, bsem,
    *,
    k: int,
    num_blocks: int,
):
    instr_dma, val_dma = _stream_dmas(instr_ref, val_ref, ibuf, vbuf, isem,
                                      vsem, k=k)
    # warm-up: b into the x buffer, block 0 in flight before the block loop
    b_dma = pltpu.make_async_copy(b_hbm_ref, x_ref, bsem)
    b_dma.start()
    instr_dma(0, 0).start()
    val_dma(0, 0).start()
    ls_ref[...] = jnp.zeros(ls_ref.shape, jnp.float32)
    b_dma.wait()

    def run_block(g, carry):
        slot = jax.lax.rem(g, 2)

        # prefetch block g+1 into the other buffer while g executes
        @pl.when(g + 1 < num_blocks)
        def _prefetch():
            instr_dma(1 - slot, g + 1).start()
            val_dma(1 - slot, g + 1).start()

        instr_dma(slot, g).wait()
        val_dma(slot, g).wait()
        _run_block(ibuf, vbuf, slot, cnt_ref[g], x_ref, None, ls_ref, k=k)
        return carry

    jax.lax.fori_loop(0, num_blocks, run_block, 0)


@functools.partial(
    jax.jit,
    static_argnames=("num_cus", "num_slots", "interpret"),
)
def sptrsv_pallas(
    instr: jnp.ndarray,    # [G * STREAM_PLANES * K] int32 (staged planes)
    values: jnp.ndarray,   # [G * K] f32 (pre-gathered stream values)
    counts: jnp.ndarray,   # [G] int32 (active entries per cycle block)
    b: jnp.ndarray,        # [n_pad, B] f32, row n_pad - 1 spare
    *,
    num_cus: int,
    num_slots: int = 12,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """VMEM-resident solve; ``instr``/``values``/``counts`` are the compacted
    stream of `ops._stage_instructions` (per cycle block: the planes, then
    the values)."""
    interpret = resolve_interpret(interpret)
    p = num_cus
    num_blocks, k = _stream_shape(instr, values, counts)
    n_pad, nb = b.shape
    state = resident_state_bytes(n_pad, nb, p, num_slots)

    kernel = functools.partial(_kernel, k=k, num_blocks=num_blocks)
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),   # instr stays in HBM
            pl.BlockSpec(memory_space=pltpu.HBM),   # values stay in HBM
            pl.BlockSpec(memory_space=pltpu.SMEM),  # per-block counts
            pl.BlockSpec(memory_space=pltpu.HBM),   # b, DMA'd into x
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, nb), jnp.float32),
        scratch_shapes=_stream_scratch(k, p, num_slots, nb) + [
            pltpu.SemaphoreType.DMA,                # bsem
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(state)),
        interpret=interpret,
    )(instr, values, counts, b)


# ---------------------------------------------------------------------------
# Row-blocked HBM-resident placement (large n)
# ---------------------------------------------------------------------------
def _blocked_kernel(
    # inputs
    instr_ref,   # [G * STREAM_PLANES * K] int32, HBM (streamed by DMA)
    val_ref,     # [G * K]                f32,   HBM (pre-gathered values)
    cnt_ref,     # [G]                    int32, SMEM (entries per block)
    b_hbm_ref,   # [n_hbm, lanes]         f32,   HBM (windowed by DMA)
    # outputs
    x_hbm_ref,   # [n_hbm, lanes]         f32,   HBM (windowed by DMA)
    # scratch
    ibuf, vbuf, ls_ref, isem, vsem,
    xwin,        # [2, window, lanes] — two x windows, top row spare
    bwin,        # [2, window, lanes] — two b windows (read-only, refetched)
    bsem, xssem, xfsem,
    *,
    k: int,
    num_blocks: int,
    window: int,
    stride: int,
):
    """x/b HBM-resident solve over a sliding VMEM row window.

    Cycle block g executes against window rows ``[g*stride, g*stride +
    window)`` of x and b held in VMEM.  Boundary g -> g+1 (async DMA):

      * flush — rows ``[g*stride, (g+1)*stride)`` leave every later window;
        the schedule's feasibility check proves no later block touches
        them, so they are final and stream out to HBM;
      * shift — the ``window - stride`` shared rows are copied into the
        other window buffer (VMEM -> VMEM, cheap).

    The b window of block g+1 and its instruction block are prefetched at
    the top of block g.  Hazard ordering: block g+1 waits on the boundary
    shift and flush before it runs, and ``window >= 2*stride`` (checked by
    the wrapper) keeps the flushed rows out of the shifted range.
    """
    w, r = window, stride
    instr_dma, val_dma = _stream_dmas(instr_ref, val_ref, ibuf, vbuf, isem,
                                      vsem, k=k)

    def b_dma(slot, g):
        return pltpu.make_async_copy(
            b_hbm_ref.at[pl.ds(g * r, w)], bwin.at[slot], bsem.at[slot])

    def x_shift_dma(src_slot, dst_slot):
        # carry the shared rows of boundary g -> g+1 across buffers
        return pltpu.make_async_copy(
            xwin.at[src_slot, pl.ds(r, w - r)],
            xwin.at[dst_slot, pl.ds(0, w - r)], xssem)

    def x_flush_dma(slot, g):
        # retire rows [g*r, g*r + r) — final, never touched again
        return pltpu.make_async_copy(
            xwin.at[slot, pl.ds(0, r)], x_hbm_ref.at[pl.ds(g * r, r)], xfsem)

    ls_ref[...] = jnp.zeros(ls_ref.shape, jnp.float32)

    # warm-up: block 0 inputs in flight before the block loop starts
    instr_dma(0, 0).start()
    val_dma(0, 0).start()
    b_dma(0, 0).start()

    def run_block(g, carry):
        slot = jax.lax.rem(g, 2)
        nxt = 1 - slot

        # inputs for block g (prefetched during g-1; warm-up for g=0)
        instr_dma(slot, g).wait()
        val_dma(slot, g).wait()
        b_dma(slot, g).wait()

        @pl.when(g > 0)
        def _assemble():
            x_shift_dma(nxt, slot).wait()   # shared rows carried over
            x_flush_dma(nxt, g - 1).wait()  # retired rows landed in HBM

        @pl.when(g + 1 < num_blocks)
        def _prefetch():
            instr_dma(nxt, g + 1).start()
            val_dma(nxt, g + 1).start()
            b_dma(nxt, g + 1).start()

        _run_block(ibuf, vbuf, slot, cnt_ref[g], xwin.at[slot], bwin.at[slot],
                   ls_ref, k=k)

        @pl.when(g + 1 < num_blocks)
        def _boundary():
            x_flush_dma(slot, g).start()
            x_shift_dma(slot, nxt).start()

        return carry

    jax.lax.fori_loop(0, num_blocks, run_block, 0)

    # final window: every still-resident row flushed in one DMA
    fin = pltpu.make_async_copy(
        xwin.at[(num_blocks - 1) % 2],
        x_hbm_ref.at[pl.ds((num_blocks - 1) * r, w)], xfsem)
    fin.start()
    fin.wait()


@functools.partial(
    jax.jit,
    static_argnames=("num_cus", "num_slots", "window", "stride",
                     "interpret"),
)
def sptrsv_pallas_blocked(
    instr: jnp.ndarray,    # [G * STREAM_PLANES * K] int32 (staged planes)
    values: jnp.ndarray,   # [G * K] f32 (pre-gathered stream values)
    counts: jnp.ndarray,   # [G] int32 (active entries per cycle block)
    b: jnp.ndarray,        # [n_hbm, B] f32 (padded to the window sweep)
    *,
    num_cus: int,
    window: int,
    stride: int,
    num_slots: int = 12,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Row-blocked HBM-resident solve (large n; see `ops.plan_window`).

    ``b`` must be padded to ``n_hbm = (num_blocks - 1) * stride + window``
    rows so every window position is in bounds; `ops.build_solver_cols`
    does this and derives a feasible (window, stride) pair from the
    program's row-range metadata.  The batch axis is padded to whole
    128-lane tiles inside: Mosaic only slices row windows out of HBM arrays
    whose minor dimension is tile-aligned, and the chip's (8, 128) layout
    stores the padding anyway.
    """
    interpret = resolve_interpret(interpret)
    p = num_cus
    num_blocks, k = _stream_shape(instr, values, counts)
    n_hbm, nb = b.shape
    lanes = -(-nb // _LANES) * _LANES
    assert stride >= 1 and window >= 2 * stride, (window, stride)
    assert n_hbm == (num_blocks - 1) * stride + window, \
        f"b rows {n_hbm} != window sweep {(num_blocks - 1) * stride + window}"
    state = blocked_state_bytes(window, lanes, p, num_slots)

    kernel = functools.partial(
        _blocked_kernel,
        k=k,
        num_blocks=num_blocks,
        window=window,
        stride=stride,
    )
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),   # instr stays in HBM
            pl.BlockSpec(memory_space=pltpu.HBM),   # values stay in HBM
            pl.BlockSpec(memory_space=pltpu.SMEM),  # per-block counts
            pl.BlockSpec(memory_space=pltpu.HBM),   # b stays in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),  # x stays in HBM
        out_shape=jax.ShapeDtypeStruct((n_hbm, lanes), jnp.float32),
        scratch_shapes=_stream_scratch(k, p, num_slots, lanes) + [
            pltpu.VMEM((2, window, lanes), jnp.float32),  # xwin
            pltpu.VMEM((2, window, lanes), jnp.float32),  # bwin
            pltpu.SemaphoreType.DMA((2,)),                # bsem
            pltpu.SemaphoreType.DMA,                      # xssem
            pltpu.SemaphoreType.DMA,                      # xfsem
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(state)),
        interpret=interpret,
    )(instr, values, counts, jnp.pad(b, ((0, 0), (0, lanes - nb))))[:, :nb]
