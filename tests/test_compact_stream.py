"""The kernels' compacted instruction streams.

The blocked kernel's lane-state stream (`ops._stage_instructions`) keeps,
per cycle block, only the lane-words that do something, in cycle-major,
lane-minor order, each pre-decoded into the rows its accesses use and its
value.  The resident kernel's row-serial stream (`ops._stage_rows`) follows
each lane's psum control once and runs the rows in the order of their
FINALs, each as its EDGE words in stream order and then its FINAL.  Either
kernel executes its entries and nothing else, branch-free, and the answer
must stay bit-identical to the float32 `lax.scan` executor.
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


def _row_entries(stream: ops.Stream):
    """``(planes [ROW_PLANES, m], values [m])`` of a row-serial stream's
    entries, the filler cut off."""
    g, m = stream.counts.shape[0], int(stream.counts.sum())
    instr = stream.instr.reshape(g, kernel.ROW_PLANES, stream.k)
    planes = instr.transpose(1, 0, 2).reshape(kernel.ROW_PLANES, -1)
    return planes[:, :m], stream.values[:m]


def _row_serial_reference(prog):
    """``(t, lane)`` of the row-serial stream, one word at a time: each
    lane's feedback and psum slots hold the list of words whose sum they
    carry (the mux of `executor.execute_numpy`), and a FINAL emits its
    list and itself."""
    op, _, ctl, slot = decode_instructions(prog.instr, prog.planes)
    p, s = prog.num_cus, _psum_slots(prog)
    fb = [[] for _ in range(p)]
    rf = [[[] for _ in range(s)] for _ in range(p)]
    out = []
    for t in range(prog.cycles):
        for li in range(p):
            o, c, q = op[t, li], ctl[t, li], min(int(slot[t, li]), s - 1)
            pv = fb[li]
            if c in (PS_RESET, PS_STORE_RESET):
                pv = []
            elif c in (PS_LOAD, PS_SWAP):
                pv = rf[li][q]
            if c in (PS_STORE_RESET, PS_SWAP):
                rf[li][q] = fb[li]
            if o == OP_EDGE:
                pv = pv + [(t, li)]
            elif o == OP_FINAL:
                out += pv + [(t, li)]
            fb[li] = pv
    return np.asarray(out, np.int64).reshape(-1, 2).T


@pytest.mark.parametrize("planes,psum_words", [(1, None), (2, None), (1, 2)])
@pytest.mark.parametrize("name", ["ckt_rajat04", "hub_small", "hpcg_8",
                                  "chain_1k"])
def test_row_serial_stream_order(name, planes, psum_words):
    """Rows come in the order of their FINALs, each as its EDGE words in
    stream order just before its FINAL; psum-only words are dropped, and
    every EDGE's source row is finished before it."""
    prog = _prog(name, planes, psum_words)
    assert prog.planes == planes
    stream = ops._stage_rows(prog, CPB)
    (src, dst), values = _row_entries(stream)
    t, lane = _row_serial_reference(prog)
    op, x_row, _, _ = decode_instructions(prog.instr, prog.planes)
    spare = ops._resident_rows(prog) - 1
    fin = (op[t, lane] == OP_FINAL).astype(int)
    assert np.array_equal(src, x_row[t, lane])
    # a FINAL stores to its own row, an EDGE to the spare row no entry loads
    assert np.array_equal(dst, np.where(fin, src, spare))
    assert (src < spare).all()
    assert np.array_equal(values, prog.stream[prog.val_idx[t, lane]]
                          .astype(np.float32))
    # psum-only words dropped, every EDGE and FINAL kept once
    assert len(src) == (op != OP_NOP).sum()
    assert len(set(zip(t.tolist(), lane.tolist()))) == len(src)
    # rows in FINAL order; within a row, its EDGEs in stream order
    word = t * prog.num_cus + lane
    assert (np.diff(word[fin == 1]) > 0).all()
    row_id = np.cumsum(np.concatenate([[0], fin[:-1]]))
    assert ((np.diff(word) > 0) | (np.diff(row_id) > 0)).all()
    # every EDGE's source row finished before it
    done = np.full(spare, len(src))
    done[src[fin == 1]] = np.flatnonzero(fin)
    edge = np.flatnonzero(fin == 0)
    assert (done[src[edge]] < edge).all()
    # blocks: k entries each, only the last one short, then the filler
    assert stream.k % kernel.SEGMENT_ALIGN == 0
    assert (stream.counts[:-1] == stream.k).all()
    assert 0 < stream.counts[-1] <= stream.k
    tail = stream.instr.reshape(-1, kernel.ROW_PLANES, stream.k)[-1]
    assert (tail[:, stream.counts[-1]:] == spare).all()
    assert not stream.values[len(src):].any()


def _edit_word(prog, t, lane, **fields):
    """``prog`` with the word at (t, lane) given new decoded fields."""
    import dataclasses

    from repro.core.program import pack_instructions

    op, src, ctl, slot = (f.copy() for f in decode_instructions(
        prog.instr, prog.planes))
    for name, value in fields.items():
        {"op": op, "src": src, "ctl": ctl, "slot": slot}[name][t, lane] = value
    return dataclasses.replace(
        prog, instr=pack_instructions(op, src, ctl, slot, planes=prog.planes))


def test_row_staging_refuses_a_read_before_the_final():
    """An EDGE edited to read a row finalised after its own row is refused:
    row-serial order would read that row before its FINAL."""
    prog = _prog("ckt_rajat04")
    op, src, _, _ = decode_instructions(prog.instr, prog.planes)
    t_fin, l_fin = np.argwhere(op == OP_FINAL)[-1]       # the last FINAL
    t, lane = np.argwhere(op == OP_EDGE)[0]               # an early EDGE
    bad = _edit_word(prog, t, lane, src=src[t_fin, l_fin])
    with pytest.raises(ValueError, match="FINAL does not come before"):
        ops._stage_rows(bad, CPB)


def test_row_staging_refuses_a_word_after_its_final():
    """A word that continues a running sum its FINAL has ended (its RESET
    edited to KEEP) is refused."""
    prog = _prog("ckt_rajat04")
    op, _, ctl, _ = decode_instructions(prog.instr, prog.planes)
    for t, lane in np.argwhere(op == OP_FINAL):
        nxt = np.flatnonzero((op[t + 1:, lane] != OP_NOP)
                             | (ctl[t + 1:, lane] != PS_KEEP))
        if len(nxt) and ctl[t + 1 + nxt[0], lane] == PS_RESET \
                and op[t + 1 + nxt[0], lane] != OP_NOP:
            bad = _edit_word(prog, t + 1 + nxt[0], lane, ctl=PS_KEEP)
            break
    else:
        pytest.fail("no FINAL followed by a RESET word on its lane")
    with pytest.raises(ValueError, match="after the FINAL"):
        ops._stage_rows(bad, CPB)


def test_row_staging_refuses_a_row_finalised_twice():
    """A FINAL edited to finalise another FINAL's row is refused: the x
    buffer's row would no longer hold b when the second one loads it."""
    prog = _prog("ckt_rajat04")
    op, src, _, _ = decode_instructions(prog.instr, prog.planes)
    (t0, l0), (t1, l1) = np.argwhere(op == OP_FINAL)[:2]
    bad = _edit_word(prog, t1, l1, src=src[t0, l0])
    with pytest.raises(ValueError, match="finalised twice"):
        ops._stage_rows(bad, CPB)


def test_row_staging_refuses_rows_outside_the_buffer():
    """A FINAL addressing an x row past n is refused at staging: the
    resident kernel uses staged rows unchecked."""
    prog = _prog("ckt_rajat04")
    op = decode_instructions(prog.instr, prog.planes)[0]
    t, lane = np.argwhere(op == OP_FINAL)[0]
    bad = _edit_word(prog, t, lane, src=ops._resident_rows(prog))
    with pytest.raises(ValueError, match="outside"):
        ops._stage_rows(bad, CPB)


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
    # the resident kernel runs the row-serial stream: the same 9,373 words
    # (no psum-only ones) in blocks of ROW_BLOCK, the last group of 8 of
    # the short last block filled out
    rows = ops._stage_rows(prog, 128)
    assert rows.counts.tolist() == [kernel.ROW_BLOCK, 9373 - kernel.ROW_BLOCK]
    assert rows.stream_words == 9376 and rows.slot_words == 16384
    solver = executor.make_pallas_executor(prog, batch=1, interpret=True)
    assert solver.placement == "resident"
    assert (solver.stream_words, solver.slot_words) == (9376, 16384)


def test_instr_buffer_bytes_counts_compacted_segments():
    prog = _prog("ckt_rajat04")
    stream = ops._stage_instructions(prog, 128)
    got = ops.instr_buffer_bytes(prog, 128, "blocked")
    assert got == 2 * stream.k * (4 * kernel.STREAM_PLANES + 4)
    # never more than the whole lane grid of one block, double-buffered
    assert got <= 2 * 128 * prog.num_cus * (4 * kernel.STREAM_PLANES + 4)
    # the resident kernel streams row-serial entries: 3 planes + the value
    rows = ops._stage_rows(prog, 128)
    got = ops.instr_buffer_bytes(prog, 128)
    assert got == 2 * rows.k * (4 * kernel.ROW_PLANES + 4)
    assert got <= 2 * kernel.ROW_BLOCK * (4 * kernel.ROW_PLANES + 4)
    acct = ops.state_bytes(prog, 1, placement="resident")
    assert acct["instr"] == got
    assert acct["vmem"] == acct["xb"]      # no lane state


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


@pytest.mark.parametrize("placement", ["resident", "blocked"])
def test_psum_starved_b16_bit_identical(placement):
    """16 columns through a psum-starved HPCG program: the resident kernel's
    running sum is one ``[1, 16]`` row carried across blocks."""
    prog = _prog("hpcg_8", psum_words=2)
    b = np.stack([random_rhs(generate("hpcg_8"), 40 + j) for j in range(16)],
                 axis=1).astype(np.float32)
    if placement == "blocked" and not ops.plan_window(prog, CPB).feasible:
        with pytest.raises(PlacementInfeasibleError):
            ops.solve(prog, b, cycles_per_block=CPB, interpret=True,
                      placement=placement)
        return
    x = ops.solve(prog, b, cycles_per_block=CPB, interpret=True,
                  placement=placement)
    assert np.array_equal(x, executor.execute_jax(prog, b))
