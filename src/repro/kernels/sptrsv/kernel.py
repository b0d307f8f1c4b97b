"""Pallas TPU kernels executing a compiled SpTRSV program.

TPU adaptation of the paper's accelerator (DESIGN.md §1):
  * the instruction stream stays in HBM (`pl.ANY`) and is streamed into
    SMEM in blocks by explicit async DMA ("data in the instruction
    memory ... is accessed sequentially", §III-B);
  * stream-memory values are pre-gathered per instruction word by the
    compiler wrapper (ops.py), so the kernel reads them sequentially too;
  * the solution vector (all of it, or the blocked kernel's windows of it)
    lives in VMEM, the software-managed scratchpad of the paper; the psum
    register file is gone: a row's running sum is a loop value.

Both kernels run one entry after another on the scalar unit: the cycle x
lane grid is a hardware notion the kernels do not need.  The scalar unit
sets the pace (one entry costs the same at B=1 and B=16), so the wrapper
hands each entry over pre-decoded as the rows its accesses use and the
kernel only loads, computes and stores.  Every access is a dynamically
indexed ``[1, B]`` row of a VMEM ref, which Mosaic lowers directly.

Row-serial stream (both kernels).  Every word of a program belongs to
exactly one row's accumulation chain: psum control parks a chain in a slot
and resumes it later, but never splits or merges one.  On a serial executor
parking buys nothing, so the wrapper (`ops._row_chains`) follows each
lane's psum control once and stages each row as its EDGE words in stream
order and then its FINAL; the resident kernel gets the rows in the order of
their FINALs (`ops._stage_rows`), the blocked kernel in ascending row order
(`ops._stage_blocked`), which in a level-ordered program is level order.  A
row's arithmetic and its order are those of the `lax.scan` executor, so the
result is bit-identical to it, and psum-only words disappear.  Each entry
has `ROW_PLANES` int32 planes and a value:

  * ``SRC`` — the x row it loads (on a FINAL its own row, which still holds
    b: x starts as b and only a row's FINAL writes it);
  * ``DST`` — the x row it stores ``(x[SRC] - psum) * v`` to: its own row on
    a FINAL, else the x ref's spare row, which no entry loads.

So an entry is a FINAL exactly when ``SRC == DST``: the running sum then
becomes zero, else ``psum + v * x[SRC]``.  The running sum is a ``[1, B]``
value carried through the loop and across blocks (a row may straddle two),
so an entry makes two row accesses and three SMEM reads; there is no lane
state.  Staging refuses a program in which an EDGE reads a row whose FINAL
does not come earlier in its order.  One entry function (`_run_rows`) serves
both kernels: the rows it addresses are staged relative to the ref it runs
on.

The loop body is one basic block, `UNROLL` entries per iteration, so the
scheduler can overlap one entry's SMEM reads with the previous entry's row
work; a per-block count table in SMEM says how many entries block g holds.
Each kernel owns two SMEM stream buffers and, while executing block g out of
one, prefetches block g+1 into the other (`pltpu.make_async_copy` + per-slot
DMA semaphores), so instruction HBM traffic overlaps compute.

Multi-RHS batching: every row carries a trailing batch axis (``x[n_pad,
B]``), so one pass over the stream solves B right-hand sides.

Two memory-placement regimes for the solve state (DESIGN.md §1):

  * `sptrsv_pallas` — x fully VMEM-resident, in one ``[n_pad, B]`` buffer
    that starts as b: b arrives in HBM and one DMA copies it into the x
    buffer.  Fastest while ``x[n_pad, B]`` fits.
  * `sptrsv_pallas_blocked` — x and b stay HBM-resident (`pl.ANY`); block g
    runs against x rows ``[g*stride, g*stride + window)``, held in a VMEM
    *ring* of ``window + 2*stride`` rows in which x row ``i`` lives at ring
    row ``i % ring`` (`ring_rows`).  x starts as b here too: block 0's
    window is DMA'd from b, the `stride` rows that enter block g+1's window
    are DMA'd from b while block g runs, and the `stride` rows that leave
    the window after block g are flushed to HBM (final: no later block
    touches them) while block g+1 runs.  No row moves inside VMEM, and the
    fill and flush never wrap, since window and ring are multiples of the
    stride.  A row enters the ring before any entry touches it and only its
    FINAL writes it, so every FINAL loads b, as in the resident kernel.

The window geometry comes from the staged stream itself (`ops.plan_window`:
every block's rows inside its window).  The wrapper stages every x row
relative to the ref it addresses (the blocked kernel's rows as their ring
rows) and checks it lies in that ref and, in the blocked kernel, in its
block's window, so a corrupt program cannot address VMEM outside the solve
state; the kernels use the staged rows as they are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    LANES,
    VMEM_HEADROOM,
    resident_state_bytes,
    resolve_interpret,
    tiled_bytes,
)

__all__ = [
    "ROW_BLOCK",
    "ROW_PLANES",
    "SEGMENT_ALIGN",
    "UNROLL",
    "sptrsv_pallas",
    "sptrsv_pallas_blocked",
    "blocked_state_bytes",
    "ring_rows",
    "vmem_limit",
]

# Default scoped-VMEM limit of a TPU v5e kernel; a kernel whose accounted
# state needs more raises its own limit (`vmem_limit`).
_SCOPED_VMEM_DEFAULT = 16 << 20
# Entries executed per iteration of the kernels' stream loops.
UNROLL = 8
# Segment length granularity: Mosaic slices a 1-D HBM array only in whole
# (1024,) tiles, and a multiple of it is a multiple of UNROLL.
SEGMENT_ALIGN = 1024
# The int32 planes of a row-serial entry: the x row it loads and the x row
# it stores; the two are equal on a FINAL alone.
SRC, DST = range(2)
ROW_PLANES = 2
# Entries per block of the row-serial stream (a SEGMENT_ALIGN multiple).
ROW_BLOCK = 8192


def vmem_limit(state_bytes: int) -> int:
    """Scoped-VMEM limit for a kernel whose solve state takes ``state_bytes``."""
    return max(_SCOPED_VMEM_DEFAULT, state_bytes + VMEM_HEADROOM)


def ring_rows(window: int, stride: int) -> int:
    """Rows of the blocked kernel's x ring: block g's window, the ``stride``
    rows entering the next window from b, and the ``stride`` rows the
    previous boundary flushes to HBM.  The x ref holds one spare row past
    the ring, which takes the x stores of entries that finalize nothing;
    no DMA carries it."""
    return window + 2 * stride


def blocked_state_bytes(window: int, stride: int, nb: int) -> int:
    """VMEM bytes of the blocked kernel: its x ring and the spare row; b is
    read from HBM as rows enter the ring, and there is no lane state."""
    return tiled_bytes(ring_rows(window, stride) + 1, nb)


def _run_rows(ibuf, vbuf, slot, count, x_ref, pv, *, k):
    """Execute the ``count`` row-serial entries in buffer ``slot``.

    ``pv`` is the ``[1, B]`` running sum the block starts with (a row may
    continue from the previous block); returns the one it ends with.
    ``ibuf`` is the flat SMEM buffer ``[2 * ROW_PLANES * k]`` of staged
    planes, ``vbuf`` the flat SMEM value buffer ``[2 * k]``.  Entries past
    ``count`` up to the next `UNROLL` multiple are filler.
    """

    def entry(e, pv):
        def plane(j):
            return ibuf[(slot * ROW_PLANES + j) * k + e]

        src, dst = plane(SRC), plane(DST)
        v = vbuf[slot * k + e]
        x_row = x_ref[pl.ds(src, 1), :]
        x_ref[pl.ds(dst, 1), :] = (x_row - pv) * v
        return jnp.where(src == dst, 0.0, pv + v * x_row)

    def step(i, pv):
        for u in range(UNROLL):
            pv = entry(i * UNROLL + u, pv)
        return pv

    return jax.lax.fori_loop(0, (count + UNROLL - 1) // UNROLL, step, pv)


def _stream_dmas(instr_ref, val_ref, ibuf, vbuf, isem, vsem, *, k, planes):
    """(instr_dma, val_dma) constructors for block g into buffer slot."""
    wblk = planes * k

    def instr_dma(slot, g):
        return pltpu.make_async_copy(
            instr_ref.at[pl.ds(g * wblk, wblk)], ibuf.at[pl.ds(slot * wblk, wblk)],
            isem.at[slot])

    def val_dma(slot, g):
        return pltpu.make_async_copy(
            val_ref.at[pl.ds(g * k, k)], vbuf.at[pl.ds(slot * k, k)],
            vsem.at[slot])

    return instr_dma, val_dma


def _stream_scratch(k, planes):
    """Scratch shared by both kernels: the double-buffered SMEM stream."""
    return [
        pltpu.SMEM((2 * planes * k,), jnp.int32),   # ibuf
        pltpu.SMEM((2 * k,), jnp.float32),          # vbuf
        pltpu.SemaphoreType.DMA((2,)),              # isem
        pltpu.SemaphoreType.DMA((2,)),              # vsem
    ]


def _stream_shape(instr, values, counts, planes):
    """(num_blocks, k) of a staged stream; checks the three agree."""
    num_blocks = counts.shape[0]
    k = values.shape[0] // num_blocks
    assert values.shape[0] == num_blocks * k and k % SEGMENT_ALIGN == 0, \
        "pad every block's segment to one SEGMENT_ALIGN multiple first"
    assert instr.shape[0] == num_blocks * planes * k, \
        "instr/values stream mismatch"
    return num_blocks, k


def _kernel(
    # inputs
    instr_ref,  # [G * ROW_PLANES * K] int32, HBM (streamed by DMA)
    val_ref,    # [G * K]              f32,   HBM (pre-gathered values)
    cnt_ref,    # [G]                  int32, SMEM (entries per block)
    b_hbm_ref,  # [n_pad, B]           f32,   HBM — copied into x once
    # outputs
    x_ref,      # [n_pad, B]           f32,   VMEM (starts as b)
    # scratch
    ibuf, vbuf, isem, vsem, bsem,
    *,
    k: int,
    num_blocks: int,
):
    instr_dma, val_dma = _stream_dmas(instr_ref, val_ref, ibuf, vbuf, isem,
                                      vsem, k=k, planes=ROW_PLANES)
    # warm-up: b into the x buffer, block 0 in flight before the block loop
    b_dma = pltpu.make_async_copy(b_hbm_ref, x_ref, bsem)
    b_dma.start()
    instr_dma(0, 0).start()
    val_dma(0, 0).start()
    b_dma.wait()

    def run_block(g, pv):
        slot = jax.lax.rem(g, 2)

        # prefetch block g+1 into the other buffer while g executes
        @pl.when(g + 1 < num_blocks)
        def _prefetch():
            instr_dma(1 - slot, g + 1).start()
            val_dma(1 - slot, g + 1).start()

        instr_dma(slot, g).wait()
        val_dma(slot, g).wait()
        return _run_rows(ibuf, vbuf, slot, cnt_ref[g], x_ref, pv, k=k)

    pv0 = jnp.zeros((1, x_ref.shape[1]), jnp.float32)
    jax.lax.fori_loop(0, num_blocks, run_block, pv0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sptrsv_pallas(
    instr: jnp.ndarray,    # [G * ROW_PLANES * K] int32 (staged planes)
    values: jnp.ndarray,   # [G * K] f32 (pre-gathered stream values)
    counts: jnp.ndarray,   # [G] int32 (entries per block)
    b: jnp.ndarray,        # [n_pad, B] f32, row n_pad - 1 spare
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """VMEM-resident solve; ``instr``/``values``/``counts`` are the
    row-serial stream of `ops._stage_rows` (per block: the planes, then the
    values)."""
    interpret = resolve_interpret(interpret)
    num_blocks, k = _stream_shape(instr, values, counts, ROW_PLANES)
    n_pad, nb = b.shape
    state = resident_state_bytes(n_pad, nb)

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
        scratch_shapes=_stream_scratch(k, ROW_PLANES) + [
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
    instr_ref,   # [G * ROW_PLANES * K] int32, HBM (streamed by DMA)
    val_ref,     # [G * K]              f32,   HBM (pre-gathered values)
    cnt_ref,     # [G]                  int32, SMEM (entries per block)
    b_hbm_ref,   # [n_hbm, lanes]       f32,   HBM (read as rows enter)
    # outputs
    x_hbm_ref,   # [n_hbm, lanes]       f32,   HBM (written as rows leave)
    # scratch
    ibuf, vbuf, isem, vsem,
    ring,        # [ring_rows + 1, lanes] — x row i at i % ring_rows, spare
    bsem, fsem,
    *,
    k: int,
    num_blocks: int,
    window: int,
    stride: int,
):
    """x/b HBM-resident solve over a sliding window of a VMEM row ring.

    Block g runs against x rows ``[g*stride, g*stride + window)``; x row i
    lives at ring row ``i % rows`` (``rows = window + 2*stride``; staging
    addressed every entry there).  x starts as b: block 0's window is
    DMA'd from b before the loop.  Around block g (async DMA):

      * fill — before it runs, the ``stride`` rows entering block g+1's
        window, ``[g*stride + window, (g+1)*stride + window)``, are DMA'd
        from b into the ring rows of ``[(g-2)*stride, (g-1)*stride)``,
        flushed at boundary g-2;
      * flush — after it runs, rows ``[g*stride, (g+1)*stride)`` leave
        every later window (staging proved no later block touches them),
        so they are final and stream out to HBM while block g+1 runs.

    Block g waits for its own fill (started at the top of block g-1) and
    for flush g-2 (whose ring rows its fill for g+1 reuses).  Window and
    ring are stride multiples, so no fill or flush wraps; flushes of odd
    and even blocks take their own semaphore, since two may be in flight.
    """
    w, r, g_n = window, stride, num_blocks
    rows = ring_rows(w, r)
    instr_dma, val_dma = _stream_dmas(instr_ref, val_ref, ibuf, vbuf, isem,
                                      vsem, k=k, planes=ROW_PLANES)

    def fill_dma(g):
        # rows entering block g's window (g >= 1), from b
        row = (g - 1) * r + w
        at = pl.multiple_of(jax.lax.rem(row, rows), 8)
        return pltpu.make_async_copy(
            b_hbm_ref.at[pl.ds(row, r)], ring.at[pl.ds(at, r)], bsem)

    def flush_dma(g):
        # retire rows [g*r, g*r + r) — final, never touched again
        at = pl.multiple_of(jax.lax.rem(g * r, rows), 8)
        return pltpu.make_async_copy(
            ring.at[pl.ds(at, r)], x_hbm_ref.at[pl.ds(g * r, r)],
            fsem.at[jax.lax.rem(g, 2)])

    # warm-up: block 0's stream and window in flight before the block loop
    instr_dma(0, 0).start()
    val_dma(0, 0).start()
    first = pltpu.make_async_copy(b_hbm_ref.at[pl.ds(0, w)],
                                  ring.at[pl.ds(0, w)], bsem)
    first.start()
    first.wait()

    def run_block(g, pv):
        slot = jax.lax.rem(g, 2)

        # inputs for block g (prefetched during g-1; warm-up for g=0)
        instr_dma(slot, g).wait()
        val_dma(slot, g).wait()

        @pl.when(g > 0)
        def _filled():
            fill_dma(g).wait()

        @pl.when(g > 1)
        def _flushed():
            flush_dma(g - 2).wait()

        # prefetch block g+1: its stream, and the rows entering its window
        @pl.when(g + 1 < g_n)
        def _prefetch():
            instr_dma(1 - slot, g + 1).start()
            val_dma(1 - slot, g + 1).start()
            fill_dma(g + 1).start()

        pv = _run_rows(ibuf, vbuf, slot, cnt_ref[g], ring, pv, k=k)

        @pl.when(g + 1 < g_n)
        def _boundary():
            flush_dma(g).start()

        return pv

    pv0 = jnp.zeros((1, ring.shape[1]), jnp.float32)
    jax.lax.fori_loop(0, g_n, run_block, pv0)
    if g_n > 1:
        flush_dma(g_n - 2).wait()

    # last window: every still-resident row, in at most two pieces where it
    # wraps around the ring
    at, row = ((g_n - 1) * r) % rows, (g_n - 1) * r
    for size in (min(w, rows - at), w - min(w, rows - at)):
        if size:
            fin = pltpu.make_async_copy(
                ring.at[pl.ds(at, size)], x_hbm_ref.at[pl.ds(row, size)],
                fsem.at[0])
            fin.start()
            fin.wait()
            at, row = 0, row + size


@functools.partial(
    jax.jit, static_argnames=("window", "stride", "interpret"))
def sptrsv_pallas_blocked(
    instr: jnp.ndarray,    # [G * ROW_PLANES * K] int32 (staged planes)
    values: jnp.ndarray,   # [G * K] f32 (pre-gathered stream values)
    counts: jnp.ndarray,   # [G] int32 (entries per block)
    b: jnp.ndarray,        # [n_hbm, B] f32 (padded to the window sweep)
    *,
    window: int,
    stride: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Row-blocked HBM-resident solve (large n; see `ops.plan_window`).

    ``instr``/``values``/``counts`` are the row-serial stream of
    `ops._stage_blocked`, its rows staged into the ring of this (window,
    stride).  ``b`` must be padded to ``n_hbm = (num_blocks - 1) *
    stride + window`` rows so every window position is in bounds;
    `ops.build_solver_cols` does this.  The batch axis is padded to whole
    128-lane tiles inside: Mosaic only slices row windows out of HBM arrays
    whose minor dimension is tile-aligned, and the chip's (8, 128) layout
    stores the padding anyway.
    """
    interpret = resolve_interpret(interpret)
    num_blocks, k = _stream_shape(instr, values, counts, ROW_PLANES)
    n_hbm, nb = b.shape
    lanes = -(-nb // LANES) * LANES
    assert stride >= 1 and window >= 2 * stride, (window, stride)
    assert stride % 8 == 0 and window % stride == 0, (window, stride)
    assert n_hbm == (num_blocks - 1) * stride + window, \
        f"b rows {n_hbm} != window sweep {(num_blocks - 1) * stride + window}"
    state = blocked_state_bytes(window, stride, lanes)

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
        scratch_shapes=_stream_scratch(k, ROW_PLANES) + [
            pltpu.VMEM((ring_rows(window, stride) + 1, lanes),
                       jnp.float32),                      # ring, spare
            pltpu.SemaphoreType.DMA,                      # bsem
            pltpu.SemaphoreType.DMA((2,)),                # fsem
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(state)),
        interpret=interpret,
    )(instr, values, counts, jnp.pad(b, ((0, 0), (0, lanes - nb))))[:, :nb]
