"""The kernel's compacted instruction stream (`ops._stage_instructions`).

Staging keeps, per cycle block, only the lane-words that do something, in
cycle-major, lane-minor order, each pre-decoded into the rows its accesses
use and its value; the kernel executes those entries and nothing else,
branch-free.  Dropping no-op words and selecting by address instead of by
value must leave the answer bit-identical to the float32 `lax.scan`
executor.
"""

import functools
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import api, executor
from repro.core.csr import random_rhs
from repro.core.errors import PlacementInfeasibleError
from repro.core.matrices import generate
from repro.core.executor import _psum_slots
from repro.core.program import (
    OP_EDGE,
    OP_FINAL,
    OP_NOP,
    PS_KEEP,
    PS_LOAD,
    PS_RESET,
    PS_STORE_RESET,
    PS_SWAP,
    AccelConfig,
    decode_instructions,
)
from repro.core.compiler import compile_program
from repro.kernels.sptrsv import kernel, ops

CPB = 64
BENCH_CONFIG = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
                / "configs" / "ckt_add20.json")


@functools.lru_cache(maxsize=None)
def _prog(name: str, planes: int | None = None, psum_words: int | None = None):
    cfg = AccelConfig(psum_words=psum_words) if psum_words else None
    return compile_program(generate(name), cfg, planes=planes)


def _unpack(stream: ops.Stream):
    """Per-block ``(planes [STREAM_PLANES, k], values [k])``."""
    g = stream.counts.shape[0]
    instr = stream.instr.reshape(g, kernel.STREAM_PLANES, stream.k)
    return instr, stream.values.reshape(g, -1)


def _expected_planes(prog, t, lane):
    """The staged planes of the words at (cycle t, lane), one word at a
    time: which rows each access of the kernel uses."""
    p, s = prog.num_cus, _psum_slots(prog)
    zero, trash = p * (s + 1), p * (s + 1) + 1
    op, src, ctl, slot = decode_instructions(prog.instr, prog.planes)
    out = []
    for ti, li in zip(t.tolist(), lane.tolist()):
        o, r, c = int(op[ti, li]), int(src[ti, li]), int(ctl[ti, li])
        slot_row = p + li * s + min(int(slot[ti, li]), s - 1)
        pv_src = {PS_KEEP: li, PS_RESET: zero, PS_STORE_RESET: zero,
                  PS_LOAD: slot_row, PS_SWAP: slot_row}[c]
        park = slot_row if c in (PS_STORE_RESET, PS_SWAP) else trash
        x_dst = r if o == OP_FINAL else ops._resident_rows(prog) - 1
        out.append([r, pv_src, li, park, x_dst, int(o == OP_EDGE)])
    return np.asarray(out, np.int64).T.reshape(kernel.STREAM_PLANES, -1)


def _active_in_order(prog, cpb):
    """(block, cycle, lane) of every active word, cycle-major, lane-minor."""
    op, _, ctl, _ = decode_instructions(prog.instr, prog.planes)
    t, lane = np.nonzero((op != OP_NOP) | (ctl != PS_KEEP))
    return t // cpb, t, lane


@pytest.mark.parametrize("name,planes", [
    ("ckt_rajat04", 1), ("band_cz", 2), ("hub_small", 1),
])
def test_staging_keeps_active_words_in_order(name, planes):
    prog = _prog(name, planes)
    stream = ops._stage_instructions(prog, CPB)
    staged, values = _unpack(stream)
    blk, t, lane = _active_in_order(prog, CPB)
    assert stream.counts.sum() == len(t)
    assert stream.counts.tolist() == np.bincount(
        blk, minlength=len(stream.counts)).tolist()
    assert stream.k % kernel.SEGMENT_ALIGN == 0
    assert stream.k >= stream.counts.max()
    vals = prog.stream[prog.val_idx].astype(np.float32)
    for g, c in enumerate(stream.counts):
        sel = blk == g
        assert np.array_equal(staged[g, :, :c],
                              _expected_planes(prog, t[sel], lane[sel]))
        assert np.array_equal(values[g, :c], vals[t[sel], lane[sel]])


def test_filler_is_nop_keep_lane_zero():
    """The filler (every slot past a block's count) is the no-op of the
    staged form: it sums from the zero row, adds no MAC, and stores only to
    the trash row and the spare x row, which no word reads."""
    prog = _prog("ckt_rajat04")
    stream = ops._stage_instructions(prog, CPB)
    staged, values = _unpack(stream)
    p, s = prog.num_cus, _psum_slots(prog)
    zero, trash = p * (s + 1), p * (s + 1) + 1
    assert kernel.lane_rows(p, s) == trash + 1
    spare = ops._resident_rows(prog) - 1
    filler = [0, zero, trash, trash, spare, 0]
    for g, c in enumerate(stream.counts):
        assert (staged[g, :, c:] == np.asarray(filler)[:, None]).all()
        assert not values[g, c:].any()
        real = staged[g, :, :c]
        assert (real[kernel.PV_SRC] != trash).all()    # trash is never read
        assert (real[kernel.FB] < p).all()
        assert ((real[kernel.PARK] >= p) & (real[kernel.PARK] != zero)).all()


@pytest.mark.parametrize("name,planes,psum_words", [
    ("ckt_rajat04", 1, None), ("band_cz", 2, None), ("hub_small", 1, None),
    ("hpcg_8", 1, None), ("ckt_rajat04", 1, 2), ("chain_1k", 1, None),
])
def test_staged_rows_stay_in_their_lane(name, planes, psum_words):
    """Every entry reads and writes only its own lane's feedback and psum
    rows, the zero row or the trash row, and stores x only to its FINAL row
    or to the spare top row of the x ref, which no word loads; in the
    blocked placement x rows are relative to their block's window."""
    prog = _prog(name, planes, psum_words)
    assert prog.planes == planes
    p, s = prog.num_cus, _psum_slots(prog)
    zero, trash = p * (s + 1), p * (s + 1) + 1
    plan = ops.plan_window(prog, CPB)
    placed = [(None, ops._resident_rows(prog), 0)]
    if plan.feasible:
        placed.append((plan, plan.window, plan.stride))
    for pl_, rows, stride in placed:
        stream = ops._stage_instructions(prog, CPB, pl_)
        staged, _ = _unpack(stream)
        blk, t, lane = _active_in_order(prog, CPB)
        src = decode_instructions(prog.instr, prog.planes)[1][t, lane]
        for g, c in enumerate(stream.counts):
            row, pv_src, fb, park, x_dst, edge = staged[g, :, :c]
            own = (p + fb * s <= pv_src) & (pv_src < p + fb * s + s)
            assert ((pv_src == fb) | (pv_src == zero) | own).all()
            own = (p + fb * s <= park) & (park < p + fb * s + s)
            assert ((park == trash) | own).all()
            assert ((x_dst == row) | (x_dst == rows - 1)).all()
            assert (0 <= row).all() and (row < rows - 1).all()
            assert np.array_equal(row + g * stride, src[blk == g])
            assert set(edge.tolist()) <= {0, 1}


def test_staging_refuses_rows_outside_the_ref():
    """A word addressing an x row past n (a corrupt program) is refused at
    staging: the kernel uses staged rows unchecked."""
    import dataclasses

    from repro.core.program import pack_instructions

    prog = _prog("ckt_rajat04")
    op, src, ctl, slot = decode_instructions(prog.instr, prog.planes)
    t, lane = np.argwhere(op == OP_FINAL)[0]
    src = src.copy()
    src[t, lane] = ops._resident_rows(prog)
    bad = dataclasses.replace(
        prog, instr=pack_instructions(op, src, ctl, slot, planes=prog.planes))
    with pytest.raises(ValueError, match="outside"):
        ops._stage_instructions(bad, CPB)


def test_ckt_add20_compaction_counts():
    """The benchmark's circuit: 9,373 active words of 254 x 64 lane slots
    padded to 256 cycles."""
    config = json.loads(BENCH_CONFIG.read_text())
    mat = generate("ckt_add20")
    assert (mat.n, mat.nnz) == (config["n"], config["fingerprint"]["nnz"])
    prog = api.compile(mat)
    assert prog.stats.emitted_cycles == 254
    stream = ops._stage_instructions(prog, 128)
    assert stream.counts.sum() == 9373
    assert stream.slot_words == 16384
    assert 9373 <= stream.stream_words <= 9373 + 2 * (kernel.UNROLL - 1)
    solver = executor.make_pallas_executor(prog, batch=1, interpret=True)
    assert (solver.stream_words, solver.slot_words) == (
        stream.stream_words, 16384)


def test_instr_buffer_bytes_counts_compacted_segments():
    prog = _prog("ckt_rajat04")
    stream = ops._stage_instructions(prog, 128)
    got = ops.instr_buffer_bytes(prog, 128)
    assert got == 2 * stream.k * (4 * kernel.STREAM_PLANES + 4)
    assert ops.state_bytes(prog, 1, placement="resident")["instr"] == got
    # never more than the whole lane grid of one block, double-buffered
    assert got <= 2 * 128 * prog.num_cus * (4 * kernel.STREAM_PLANES + 4)


@pytest.mark.parametrize("placement", ["resident", "blocked"])
@pytest.mark.parametrize("planes", [1, 2])
@pytest.mark.parametrize("batch", [1, 16])
@pytest.mark.parametrize("name", ["band_cz", "ckt_rajat04", "hub_small",
                                  "hpcg_8", "chain_1k"])
def test_kernel_bit_identical_to_scan(name, batch, planes, placement):
    prog = _prog(name, planes)
    assert prog.planes == planes
    b = np.stack([random_rhs(generate(name), 7 + j) for j in range(batch)],
                 axis=1).astype(np.float32)
    if placement == "blocked" and not ops.plan_window(prog, CPB).feasible:
        with pytest.raises(PlacementInfeasibleError):
            ops.solve(prog, b, cycles_per_block=CPB, interpret=True,
                      placement=placement)
        return
    x = ops.solve(prog, b, cycles_per_block=CPB, interpret=True,
                  placement=placement)
    assert np.array_equal(x, executor.execute_jax(prog, b))


@pytest.mark.parametrize("name,placement", [
    ("ckt_rajat04", "resident"), ("chem_bp", "resident"), ("chem_bp", "blocked"),
    ("hpcg_8", "resident"), ("chain_1k", "resident"), ("chain_1k", "blocked"),
])
def test_psum_starved_program_bit_identical(name, placement):
    """Two psum words per lane force slot spills: SWAP, STORE_RESET and LOAD
    entries run through the select-stores."""
    prog = _prog(name, psum_words=2)
    _, _, ctl, _ = decode_instructions(prog.instr, prog.planes)
    for c in (PS_LOAD, PS_STORE_RESET, PS_SWAP):
        assert (ctl == c).any(), c
    if placement == "blocked":
        assert ops.plan_window(prog, CPB).feasible
    b = np.stack([random_rhs(generate(name), j) for j in range(3)],
                 axis=1).astype(np.float32)
    x = ops.solve(prog, b, cycles_per_block=CPB, interpret=True,
                  placement=placement)
    assert np.array_equal(x, executor.execute_jax(prog, b))
