"""The kernels' compacted instruction streams.

Both kernels run a row-serial stream: staging follows each lane's psum
control once (`ops._row_chains`) and runs each row as its EDGE words in
stream order and then its FINAL, each entry pre-decoded into the rows its
accesses use and its value.  The resident kernel's stream (`ops._stage_rows`)
has the rows in the order of their FINALs; the blocked kernel's
(`ops._stage_blocked`) in ascending row order, its rows staged into the
window of their block.  Either kernel executes its entries and nothing else,
branch-free, and the answer must stay bit-identical to the float32
`lax.scan` executor.
"""

import dataclasses
import functools
import hashlib
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

BENCH_CONFIG = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
                / "configs" / "ckt_add20.json")


@functools.lru_cache(maxsize=None)
def _prog(name: str, planes: int | None = None, psum_words: int | None = None):
    cfg = AccelConfig(psum_words=psum_words) if psum_words else None
    return compile_program(generate(name), cfg, planes=planes)


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
    stream = ops._stage_rows(prog)
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


def _ascending(t, lane, prog):
    """The row-serial reference's words regrouped row by row in ascending
    row order, each row's words kept in their order."""
    op, x_row, _, _ = decode_instructions(prog.instr, prog.planes)
    chains, cur = [], []
    for w in zip(t.tolist(), lane.tolist()):
        cur.append(w)
        if op[w] == OP_FINAL:
            chains.append((int(x_row[w]), cur))
            cur = []
    chains.sort(key=lambda c: c[0])
    return np.asarray([w for _, c in chains for w in c], np.int64).T


@pytest.mark.parametrize("name,planes", [
    ("ckt_rajat04", 1), ("band_cz", 2), ("hub_small", 1),
])
def test_blocked_staging_keeps_rows_in_ascending_order(name, planes):
    """The blocked kernel's entries are the resident kernel's, regrouped in
    ascending row order: each row its EDGE words in stream order, then its
    FINAL; every EDGE's source row is finished before it."""
    prog = _prog(name, planes)
    assert prog.planes == planes
    t, lane, src, fin = ops._blocked_order(prog)
    want_t, want_lane = _ascending(*_row_serial_reference(prog), prog)
    assert np.array_equal(t, want_t) and np.array_equal(lane, want_lane)
    op, x_row, _, _ = decode_instructions(prog.instr, prog.planes)
    assert np.array_equal(src, x_row[t, lane])
    assert np.array_equal(fin, op[t, lane] == OP_FINAL)
    assert (np.diff(src[fin]) > 0).all()
    done = np.full(prog.n, len(src))
    done[src[fin]] = np.flatnonzero(fin)
    assert (done[src[~fin]] < np.flatnonzero(~fin)).all()


@pytest.mark.usefixtures("small_row_blocks")
def test_blocked_filler_is_a_final_of_the_spare_row():
    """The filler past the last block's count loads and stores the spare row
    past the ring with value 0, and no real entry loads that row."""
    prog = _prog("band_cz")
    plan = ops.plan_window(prog)
    stream = ops._stage_blocked(prog, plan)
    (src, dst), values = _row_entries(stream)
    spare = kernel.ring_rows(plan.window, plan.stride)
    tail = stream.instr.reshape(-1, kernel.ROW_PLANES, stream.k)[-1]
    assert plan.num_blocks > 1 and stream.counts[-1] < stream.k
    assert (tail[:, stream.counts[-1]:] == spare).all()
    assert not stream.values[len(src):].any()
    assert (src < spare).all() and ((dst == src) | (dst == spare)).all()


@pytest.mark.usefixtures("small_row_blocks")
@pytest.mark.parametrize("name,planes,psum_words", [
    ("ckt_rajat04", 1, None), ("band_cz", 2, None), ("hub_small", 1, None),
    ("hpcg_8", 1, None), ("ckt_rajat04", 1, 2), ("chain_1k", 1, None),
])
def test_blocked_rows_stay_in_their_window(name, planes, psum_words,
                                           monkeypatch):
    """Every entry loads and stores only rows of its block's window, at
    their ring rows, or stores to the spare row past the ring.  Hub DAGs
    keep an early row live across the whole stream: no window slides, and
    one block holding the whole stream is their only plan."""
    prog = _prog(name, planes, psum_words)
    assert prog.planes == planes
    plan = ops.plan_window(prog)
    if not plan.feasible:
        assert name in ("ckt_rajat04", "hub_small")
        monkeypatch.setattr(ops, "ROW_BLOCK", 8 * kernel.SEGMENT_ALIGN)
        plan = ops.plan_window(prog)
        assert plan.feasible and plan.num_blocks == 1
    stream = ops._stage_blocked(prog, plan)
    _, _, rows, fin = ops._blocked_order(prog)
    (src, dst), _ = _row_entries(stream)
    blk = np.arange(len(src)) // stream.k
    lo = blk * plan.stride
    assert ((lo <= rows) & (rows < lo + plan.window)).all()
    ring = kernel.ring_rows(plan.window, plan.stride)
    assert np.array_equal(src, rows % ring)
    assert np.array_equal(dst, np.where(fin, src, ring))


def _edit_word(prog, t, lane, **fields):
    """``prog`` with the word at (t, lane) given new decoded fields."""
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
        ops._stage_rows(bad)


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
        ops._stage_rows(bad)


def test_row_staging_refuses_a_row_finalised_twice():
    """A FINAL edited to finalise another FINAL's row is refused: the x
    buffer's row would no longer hold b when the second one loads it."""
    prog = _prog("ckt_rajat04")
    op, src, _, _ = decode_instructions(prog.instr, prog.planes)
    (t0, l0), (t1, l1) = np.argwhere(op == OP_FINAL)[:2]
    bad = _edit_word(prog, t1, l1, src=src[t0, l0])
    with pytest.raises(ValueError, match="finalised twice"):
        ops._stage_rows(bad)


def test_row_staging_refuses_rows_outside_the_buffer():
    """A FINAL addressing an x row past n is refused at staging: the
    resident kernel uses staged rows unchecked."""
    prog = _prog("ckt_rajat04")
    op = decode_instructions(prog.instr, prog.planes)[0]
    t, lane = np.argwhere(op == OP_FINAL)[0]
    bad = _edit_word(prog, t, lane, src=ops._resident_rows(prog))
    with pytest.raises(ValueError, match="outside"):
        ops._stage_rows(bad)


@pytest.mark.usefixtures("small_row_blocks")
@pytest.mark.parametrize("fault", ["row past the buffer", "window too small",
                                   "edge before its final"])
def test_blocked_staging_refuses(fault):
    """The blocked kernel uses staged rows unchecked too: staging refuses a
    row past the x buffer, a plan whose window does not hold a block's
    rows, and an EDGE whose source row is finalised after it."""
    prog = _prog("band_cz")
    plan = ops.plan_window(prog)
    op, src, _, _ = decode_instructions(prog.instr, prog.planes)
    if fault == "row past the buffer":
        t, lane = np.argwhere(op == OP_FINAL)[0]
        prog = _edit_word(prog, t, lane, src=ops._resident_rows(prog))
        match = "outside"
    elif fault == "window too small":
        plan = dataclasses.replace(plan, window=8)
        match = "outside its block"
    else:
        t_fin, l_fin = np.argwhere(op == OP_FINAL)[-1]   # the last row
        t, lane = np.argwhere(op == OP_EDGE)[0]          # an early EDGE
        prog = _edit_word(prog, t, lane, src=src[t_fin, l_fin])
        match = "FINAL does not come before"
    with pytest.raises(ValueError, match=match):
        ops._stage_blocked(prog, plan)


def test_ckt_add20_compaction_counts():
    """The benchmark's circuit: 9,373 words that do arithmetic, of 254 x 64
    lane slots."""
    config = json.loads(BENCH_CONFIG.read_text())
    mat = generate("ckt_add20")
    assert (mat.n, mat.nnz) == (config["n"], config["fingerprint"]["nnz"])
    prog = api.compile(mat)
    assert prog.stats.emitted_cycles == 254
    op = decode_instructions(prog.instr, prog.planes)[0]
    assert (op != OP_NOP).sum() == 9373
    # the resident kernel runs the row-serial stream: the same 9,373 words
    # (no psum-only ones) in blocks of ROW_BLOCK, the last group of 8 of
    # the short last block filled out
    rows = ops._stage_rows(prog)
    assert rows.counts.tolist() == [kernel.ROW_BLOCK, 9373 - kernel.ROW_BLOCK]
    assert rows.stream_words == 9376 and rows.slot_words == 254 * 64
    solver = executor.make_pallas_executor(prog, batch=1, interpret=True)
    assert solver.placement == "resident"
    assert (solver.stream_words, solver.slot_words) == (9376, 254 * 64)


# sha256 of `_stage_rows` (instr, values, counts, k) at the commit before
# the blocked kernel took the row-serial stream: the resident kernel's
# stream must not move with it
STAGE_ROWS_DIGESTS = {
    "ckt_add20":
        "3edefc30b2f78d3a8d9176d16a145d681798fbac6fcb54bc94ddf666ccb4f1f0",
    "hpcg_8":
        "76d2fd70c5f9ee64d90b6e2df22ced646a9432b944188ca7d14f2281a9b78677",
}


@pytest.mark.parametrize("name", sorted(STAGE_ROWS_DIGESTS))
def test_resident_stream_unchanged(name):
    stream = ops._stage_rows(api.compile(generate(name)))
    h = hashlib.sha256()
    for a in (stream.instr, stream.values, stream.counts):
        h.update(np.ascontiguousarray(a).tobytes())
        h.update(str(a.dtype).encode())
    h.update(str(stream.k).encode())
    assert h.hexdigest() == STAGE_ROWS_DIGESTS[name]


@pytest.mark.parametrize("name", ["ckt_rajat04", "band_cz"])
def test_instr_buffer_bytes_counts_row_blocks(name):
    """Both kernels stream the same row-serial entries: two buffers of K
    entries of ROW_PLANES int32 planes and a value, K at most ROW_BLOCK."""
    prog = _prog(name)
    rows = ops._stage_rows(prog)
    got = ops.instr_buffer_bytes(prog)
    assert got == 2 * rows.k * (4 * kernel.ROW_PLANES + 4)
    assert got <= 2 * kernel.ROW_BLOCK * (4 * kernel.ROW_PLANES + 4)
    acct = ops.state_bytes(prog, 1, placement="resident")
    assert acct["instr"] == got
    assert acct["vmem"] == acct["xb"]      # no lane state
    plan = ops.plan_window(prog)
    if plan.feasible:
        assert ops._stage_blocked(prog, plan).k == rows.k
        acct = ops.state_bytes(prog, 1, placement="blocked", plan=plan)
        assert acct["instr"] == got
        assert acct["vmem"] == acct["xb"] == plan.state_bytes(1)


@pytest.mark.parametrize("placement", ["resident", "blocked"])
@pytest.mark.parametrize("planes", [1, 2])
@pytest.mark.parametrize("batch", [1, 16])
@pytest.mark.parametrize("name", ["band_cz", "ckt_rajat04", "hub_small",
                                  "hpcg_8", "chain_1k"])
def test_kernel_bit_identical_to_scan(name, batch, planes, placement,
                                      request):
    if placement == "blocked":
        request.getfixturevalue("small_row_blocks")
    prog = _prog(name, planes)
    assert prog.planes == planes
    b = np.stack([random_rhs(generate(name), 7 + j) for j in range(batch)],
                 axis=1).astype(np.float32)
    if placement == "blocked" and not ops.plan_window(prog).feasible:
        with pytest.raises(PlacementInfeasibleError):
            ops.solve(prog, b, interpret=True,
                      placement=placement)
        return
    x = ops.solve(prog, b, interpret=True,
                  placement=placement)
    assert np.array_equal(x, executor.execute_jax(prog, b))


@pytest.mark.parametrize("name,placement", [
    ("ckt_rajat04", "resident"), ("chem_bp", "resident"), ("chem_bp", "blocked"),
    ("hpcg_8", "resident"), ("chain_1k", "resident"), ("chain_1k", "blocked"),
])
def test_psum_starved_program_bit_identical(name, placement, request):
    """Two psum words per lane force slot spills: SWAP, STORE_RESET and LOAD
    words, whose chains staging follows into rows."""
    if placement == "blocked":
        request.getfixturevalue("small_row_blocks")
    prog = _prog(name, psum_words=2)
    _, _, ctl, _ = decode_instructions(prog.instr, prog.planes)
    for c in (PS_LOAD, PS_STORE_RESET, PS_SWAP):
        assert (ctl == c).any(), c
    if placement == "blocked":
        assert ops.plan_window(prog).feasible
    b = np.stack([random_rhs(generate(name), j) for j in range(3)],
                 axis=1).astype(np.float32)
    x = ops.solve(prog, b, interpret=True,
                  placement=placement)
    assert np.array_equal(x, executor.execute_jax(prog, b))


@pytest.mark.parametrize("placement", ["resident", "blocked"])
def test_psum_starved_b16_bit_identical(placement, request):
    """16 columns through a psum-starved HPCG program: the running sum is
    one ``[1, 16]`` row carried across blocks."""
    if placement == "blocked":
        request.getfixturevalue("small_row_blocks")
    prog = _prog("hpcg_8", psum_words=2)
    b = np.stack([random_rhs(generate("hpcg_8"), 40 + j) for j in range(16)],
                 axis=1).astype(np.float32)
    if placement == "blocked" and not ops.plan_window(prog).feasible:
        with pytest.raises(PlacementInfeasibleError):
            ops.solve(prog, b, interpret=True,
                      placement=placement)
        return
    x = ops.solve(prog, b, interpret=True,
                  placement=placement)
    assert np.array_equal(x, executor.execute_jax(prog, b))
