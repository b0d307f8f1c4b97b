"""Pallas TPU kernel executing a compiled SpTRSV VLIW instruction stream.

TPU adaptation of the paper's accelerator (DESIGN.md §1):
  * the instruction stream stays in HBM (`pl.ANY`) and is streamed into
    SMEM in cycle blocks by explicit async DMA ("data in the instruction
    memory ... is accessed sequentially", §III-B);
  * stream-memory values are pre-gathered per instruction word by the
    compiler wrapper (ops.py), so the kernel reads them sequentially too;
  * the solution vector, the feedback registers and the psum register file
    live in VMEM refs (the software-managed scratchpads of the paper).

Per-lane execution.  A VLIW cycle is run as a scalar-driven loop over the
P compute units: each lane's packed word is read from SMEM and decoded on
the scalar unit (`program.decode_word`), and its row operands move as
single ``[1, B]`` rows addressed by those scalars — ``x[src]`` /
``b[src]`` loads, the FINAL ``x[src]`` store, and the lane's own feedback
and psum-slot rows.  Lanes whose word is a no-op (op NOP, psum KEEP) are
skipped.  Every access is a dynamically indexed row of a VMEM ref, which
Mosaic lowers directly; no vector gather/scatter by 64 independent indices
is needed.  Lanes of one cycle are independent (the scheduler guarantees
an EDGE only reads x rows finalized in earlier cycles and FINAL rows are
distinct), so running them one after another is exactly the synchronous
cycle semantics of the numpy oracle.

Double-buffered cycle-block streaming: the kernel owns two SMEM instruction
buffers and, while executing cycle block g out of one buffer, prefetches
block g+1 into the other (`pltpu.make_async_copy` + per-slot DMA
semaphores), so instruction HBM traffic overlaps compute.

Multi-RHS batching: every row carries a trailing batch axis (``x[n_pad,
B]``, ``feedback[P, B]``, ``rf[P * S, B]``), so one pass over the
instruction stream solves B right-hand sides.

Two memory-placement regimes for the solve state (DESIGN.md §1):

  * `sptrsv_pallas` — x and b fully VMEM-resident.  Fastest while
    ``x[n_pad, B]`` + ``b[n_pad, B]`` fit.
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
kernel still clamps every row index into its ref, so a corrupt program
cannot address VMEM outside the solve state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.program import (
    OP_EDGE,
    OP_FINAL,
    OP_NOP,
    PS_KEEP,
    PS_LOAD,
    PS_RESET,
    PS_STORE_RESET,
    PS_SWAP,
    decode_word,
)
from repro.kernels.common import resolve_interpret

__all__ = [
    "sptrsv_pallas",
    "sptrsv_pallas_blocked",
    "blocked_state_bytes",
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


def tiled_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of a ``[rows, cols]`` f32 array in (8, 128) tiles."""
    return (-(-rows // _SUBLANES) * _SUBLANES) * (-(-cols // _LANES) * _LANES) * 4


def vmem_limit(state_bytes: int) -> int:
    """Scoped-VMEM limit for a kernel whose solve state takes ``state_bytes``."""
    return max(_SCOPED_VMEM_DEFAULT, state_bytes + _VMEM_HEADROOM)


def _lane_state_bytes(p: int, num_slots: int, nb: int) -> int:
    """VMEM bytes of the per-lane feedback rows and psum register file."""
    return tiled_bytes(p, nb) + tiled_bytes(p * num_slots, nb)


def resident_state_bytes(n_pad: int, nb: int, p: int, num_slots: int) -> int:
    """VMEM bytes of the resident kernel: x and b + lane state."""
    return 2 * tiled_bytes(n_pad, nb) + _lane_state_bytes(p, num_slots, nb)


def blocked_state_bytes(window: int, nb: int, p: int, num_slots: int) -> int:
    """VMEM bytes of the blocked kernel: two x and two b windows + lane state."""
    return 4 * tiled_bytes(window, nb) + _lane_state_bytes(p, num_slots, nb)


def _run_block(ibuf, vbuf, slot, x_ref, b_ref, fb_ref, rf_ref, *, base,
               rows, tb, p, planes, num_slots):
    """Execute the ``tb`` cycles held in instruction buffer ``slot``.

    ``x_ref``/``b_ref`` hold solution/RHS rows ``[base, base + rows)`` (the
    whole padded vector with ``base=0`` in the VMEM-resident kernel, the
    sliding window in the blocked one).  ``ibuf`` is the flat SMEM word
    buffer ``[2 * tb * planes * p]``, ``vbuf`` the flat SMEM value buffer
    ``[2 * tb * p]``.
    """

    def lane(t, l):
        w = (slot * tb + t) * planes * p + l
        op, src, ct, sl = decode_word(
            ibuf[w], ibuf[w + p] if planes == 2 else None)

        @pl.when((op != OP_NOP) | (ct != PS_KEEP))
        def _():
            v = vbuf[(slot * tb + t) * p + l]
            fb = fb_ref[pl.ds(l, 1), :]
            r = l * num_slots + jnp.minimum(sl, num_slots - 1)
            slot_val = rf_ref[pl.ds(r, 1), :]
            # psum control mux (S1/S2 of Fig. 4b)
            pv = jnp.where(ct == PS_RESET, 0.0, fb)
            pv = jnp.where(ct == PS_LOAD, slot_val, pv)

            @pl.when((ct == PS_STORE_RESET) | (ct == PS_SWAP))
            def _():
                rf_ref[pl.ds(r, 1), :] = fb

            pv = jnp.where(ct == PS_STORE_RESET, 0.0, pv)
            pv = jnp.where(ct == PS_SWAP, slot_val, pv)

            row = jnp.clip(src - base, 0, rows - 1)
            pv = jnp.where(op == OP_EDGE, pv + v * x_ref[pl.ds(row, 1), :], pv)

            @pl.when(op == OP_FINAL)
            def _():
                x_ref[pl.ds(row, 1), :] = (b_ref[pl.ds(row, 1), :] - pv) * v

            fb_ref[pl.ds(l, 1), :] = pv

    def cycle(t, carry):
        def step(l, c):
            lane(t, l)
            return c

        return jax.lax.fori_loop(0, p, step, carry)

    jax.lax.fori_loop(0, tb, cycle, 0)


def _stream_dmas(instr_ref, val_ref, ibuf, vbuf, isem, vsem, *, tb, p, planes):
    """(instr_dma, val_dma) constructors for cycle block g into buffer slot."""
    wblk, vblk = tb * planes * p, tb * p

    def instr_dma(slot, g):
        return pltpu.make_async_copy(
            instr_ref.at[pl.ds(g * wblk, wblk)], ibuf.at[pl.ds(slot * wblk, wblk)],
            isem.at[slot])

    def val_dma(slot, g):
        return pltpu.make_async_copy(
            val_ref.at[pl.ds(g * vblk, vblk)], vbuf.at[pl.ds(slot * vblk, vblk)],
            vsem.at[slot])

    return instr_dma, val_dma


def _stream_scratch(tb, p, planes, num_slots, nb):
    """Scratch shared by both kernels: SMEM stream buffers + lane state."""
    return [
        pltpu.SMEM((2 * tb * planes * p,), jnp.int32),  # ibuf
        pltpu.SMEM((2 * tb * p,), jnp.float32),         # vbuf
        pltpu.VMEM((p, nb), jnp.float32),               # feedback
        pltpu.VMEM((p * num_slots, nb), jnp.float32),   # psum register file
        pltpu.SemaphoreType.DMA((2,)),                  # isem
        pltpu.SemaphoreType.DMA((2,)),                  # vsem
    ]


def _kernel(
    # inputs
    instr_ref,  # [T * planes * P] int32, HBM (streamed by DMA)
    val_ref,    # [T * P]          f32,   HBM (pre-gathered values)
    b_ref,      # [n_pad, B]       f32,   VMEM — loaded once per solve
    # outputs
    x_ref,      # [n_pad, B]       f32,   VMEM
    # scratch
    ibuf, vbuf, fb_ref, rf_ref, isem, vsem,
    *,
    cycles_per_block: int,
    num_blocks: int,
    num_slots: int,
    planes: int,
    p: int,
):
    tb = cycles_per_block
    instr_dma, val_dma = _stream_dmas(instr_ref, val_ref, ibuf, vbuf, isem,
                                      vsem, tb=tb, p=p, planes=planes)
    x_ref[...] = jnp.zeros(x_ref.shape, jnp.float32)
    fb_ref[...] = jnp.zeros(fb_ref.shape, jnp.float32)
    rf_ref[...] = jnp.zeros(rf_ref.shape, jnp.float32)

    # warm-up: block 0 in flight before the block loop starts
    instr_dma(0, 0).start()
    val_dma(0, 0).start()

    def run_block(g, carry):
        slot = jax.lax.rem(g, 2)

        # prefetch block g+1 into the other buffer while g executes
        @pl.when(g + 1 < num_blocks)
        def _prefetch():
            instr_dma(1 - slot, g + 1).start()
            val_dma(1 - slot, g + 1).start()

        instr_dma(slot, g).wait()
        val_dma(slot, g).wait()
        _run_block(ibuf, vbuf, slot, x_ref, b_ref, fb_ref, rf_ref, base=0,
                   rows=x_ref.shape[0], tb=tb, p=p, planes=planes,
                   num_slots=num_slots)
        return carry

    jax.lax.fori_loop(0, num_blocks, run_block, 0)


@functools.partial(
    jax.jit,
    static_argnames=("num_cus", "planes", "cycles_per_block", "num_slots",
                     "interpret"),
)
def sptrsv_pallas(
    instr: jnp.ndarray,    # [T * planes * P] packed int32 (T a block multiple)
    values: jnp.ndarray,   # [T * P] f32 (pre-gathered stream values)
    b: jnp.ndarray,        # [n_pad, B] f32
    *,
    num_cus: int,
    planes: int,
    cycles_per_block: int = 128,
    num_slots: int = 12,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """VMEM-resident solve; ``instr``/``values`` are the flat staged streams
    of `ops.build_solver_cols` (cycle-major, then plane, then lane)."""
    interpret = resolve_interpret(interpret)
    p = num_cus
    assert planes in (1, 2), f"expected packed 1- or 2-plane words, got {planes}"
    t = values.shape[0] // p
    assert instr.shape[0] == t * planes * p, "instr/values stream mismatch"
    assert t % cycles_per_block == 0, "pad the instruction stream first"
    n_pad, nb = b.shape
    state = resident_state_bytes(n_pad, nb, p, num_slots)

    kernel = functools.partial(
        _kernel,
        cycles_per_block=cycles_per_block,
        num_blocks=t // cycles_per_block,
        num_slots=num_slots,
        planes=planes,
        p=p,
    )
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),   # instr stays in HBM
            pl.BlockSpec(memory_space=pltpu.HBM),   # values stay in HBM
            pl.BlockSpec(memory_space=pltpu.VMEM),  # b loaded once
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, nb), jnp.float32),
        scratch_shapes=_stream_scratch(cycles_per_block, p, planes, num_slots,
                                       nb),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(state)),
        interpret=interpret,
    )(instr, values, b)


# ---------------------------------------------------------------------------
# Row-blocked HBM-resident placement (large n)
# ---------------------------------------------------------------------------
def _blocked_kernel(
    # inputs
    instr_ref,   # [T * planes * P] int32, HBM (streamed by DMA)
    val_ref,     # [T * P]          f32,   HBM (pre-gathered values)
    b_hbm_ref,   # [n_hbm, lanes]   f32,   HBM (windowed by DMA)
    # outputs
    x_hbm_ref,   # [n_hbm, lanes]   f32,   HBM (windowed by DMA)
    # scratch
    ibuf, vbuf, fb_ref, rf_ref, isem, vsem,
    xwin,        # [2, window, lanes] — two x windows
    bwin,        # [2, window, lanes] — two b windows (read-only, refetched)
    bsem, xssem, xfsem,
    *,
    cycles_per_block: int,
    num_blocks: int,
    num_slots: int,
    window: int,
    stride: int,
    planes: int,
    p: int,
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
    tb = cycles_per_block
    w, r = window, stride
    instr_dma, val_dma = _stream_dmas(instr_ref, val_ref, ibuf, vbuf, isem,
                                      vsem, tb=tb, p=p, planes=planes)

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

    fb_ref[...] = jnp.zeros(fb_ref.shape, jnp.float32)
    rf_ref[...] = jnp.zeros(rf_ref.shape, jnp.float32)

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

        _run_block(ibuf, vbuf, slot, xwin.at[slot], bwin.at[slot], fb_ref,
                   rf_ref, base=g * r, rows=w, tb=tb, p=p, planes=planes,
                   num_slots=num_slots)

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
    static_argnames=("num_cus", "planes", "cycles_per_block", "num_slots",
                     "window", "stride", "interpret"),
)
def sptrsv_pallas_blocked(
    instr: jnp.ndarray,    # [T * planes * P] packed int32 (T a block multiple)
    values: jnp.ndarray,   # [T * P] f32 (pre-gathered stream values)
    b: jnp.ndarray,        # [n_hbm, B] f32 (padded to the window sweep)
    *,
    num_cus: int,
    planes: int,
    window: int,
    stride: int,
    cycles_per_block: int = 128,
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
    assert planes in (1, 2), f"expected packed 1- or 2-plane words, got {planes}"
    t = values.shape[0] // p
    assert instr.shape[0] == t * planes * p, "instr/values stream mismatch"
    assert t % cycles_per_block == 0, "pad the instruction stream first"
    num_blocks = t // cycles_per_block
    n_hbm, nb = b.shape
    lanes = -(-nb // _LANES) * _LANES
    assert stride >= 1 and window >= 2 * stride, (window, stride)
    assert n_hbm == (num_blocks - 1) * stride + window, \
        f"b rows {n_hbm} != window sweep {(num_blocks - 1) * stride + window}"
    state = blocked_state_bytes(window, lanes, p, num_slots)

    kernel = functools.partial(
        _blocked_kernel,
        cycles_per_block=cycles_per_block,
        num_blocks=num_blocks,
        num_slots=num_slots,
        window=window,
        stride=stride,
        planes=planes,
        p=p,
    )
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),   # instr stays in HBM
            pl.BlockSpec(memory_space=pltpu.HBM),   # values stay in HBM
            pl.BlockSpec(memory_space=pltpu.HBM),   # b stays in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),  # x stays in HBM
        out_shape=jax.ShapeDtypeStruct((n_hbm, lanes), jnp.float32),
        scratch_shapes=_stream_scratch(cycles_per_block, p, planes, num_slots,
                                       lanes) + [
            pltpu.VMEM((2, window, lanes), jnp.float32),  # xwin
            pltpu.VMEM((2, window, lanes), jnp.float32),  # bwin
            pltpu.SemaphoreType.DMA((2,)),                # bsem
            pltpu.SemaphoreType.DMA,                      # xssem
            pltpu.SemaphoreType.DMA,                      # xfsem
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(state)),
        interpret=interpret,
    )(instr, values, jnp.pad(b, ((0, 0), (0, lanes - nb))))[:, :nb]
