"""The kernel's compacted instruction stream (`ops._stage_instructions`).

Staging keeps, per cycle block, only the lane-words that do something, in
cycle-major, lane-minor order, with their lane ids and values; the kernel
executes those entries and nothing else, branch-free.  Dropping no-op words
and writing back values read in the same order must leave the answer
bit-identical to the float32 `lax.scan` executor.
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
from repro.core.program import (
    OP_NOP,
    PS_KEEP,
    PS_LOAD,
    PS_STORE_RESET,
    PS_SWAP,
    AccelConfig,
    decode_instructions,
)
from repro.core.schedule import compile_program
from repro.kernels.sptrsv import kernel, ops

CPB = 64
BENCH_CONFIG = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
                / "configs" / "ckt_add20.json")


@functools.lru_cache(maxsize=None)
def _prog(name: str, planes: int | None = None, psum_words: int | None = None):
    cfg = AccelConfig(psum_words=psum_words) if psum_words else None
    return compile_program(generate(name), cfg, planes=planes)


def _unpack(stream: ops.Stream, planes: int):
    """Per-block ``(words [planes, k], lanes [k], values [k])``."""
    g = stream.counts.shape[0]
    instr = stream.instr.reshape(g, planes + 1, stream.k)
    return instr[:, :planes], instr[:, planes], stream.values.reshape(g, -1)


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
    words, lanes, values = _unpack(stream, planes)
    blk, t, lane = _active_in_order(prog, CPB)
    assert stream.counts.sum() == len(t)
    assert stream.counts.tolist() == np.bincount(
        blk, minlength=len(stream.counts)).tolist()
    assert stream.k % kernel.SEGMENT_ALIGN == 0
    assert stream.k >= stream.counts.max()
    vals = prog.stream[prog.val_idx].astype(np.float32)
    for g, c in enumerate(stream.counts):
        sel = blk == g
        kept = prog.instr[t[sel], :, lane[sel]].T  # [planes, count]
        assert np.array_equal(words[g, :, :c], kept)
        assert np.array_equal(lanes[g, :c], lane[sel])
        assert np.array_equal(values[g, :c], vals[t[sel], lane[sel]])


def test_filler_is_nop_keep_lane_zero():
    prog = _prog("ckt_rajat04")
    stream = ops._stage_instructions(prog, CPB)
    words, lanes, values = _unpack(stream, prog.planes)
    for g, c in enumerate(stream.counts):
        op, _, ctl, _ = decode_instructions(words[g, :, c:], prog.planes)
        assert (op == OP_NOP).all() and (ctl == PS_KEEP).all()
        assert not words[g, :, c:].any()
        assert not lanes[g, c:].any() and not values[g, c:].any()


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
    assert got == 2 * stream.k * (4 * prog.planes + 4 + 4)
    assert ops.state_bytes(prog, 1, placement="resident")["instr"] == got
    # never more than the whole lane grid of one block, double-buffered
    assert got <= 2 * 128 * prog.num_cus * (4 * prog.planes + 8)


@pytest.mark.parametrize("placement", ["resident", "blocked"])
@pytest.mark.parametrize("planes", [1, 2])
@pytest.mark.parametrize("batch", [1, 16])
@pytest.mark.parametrize("name", ["band_cz", "ckt_rajat04", "hub_small",
                                  "hpcg_8"])
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
    ("hpcg_8", "resident"),
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
