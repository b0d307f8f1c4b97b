"""Packed single-word VLIW instruction encoding (DESIGN.md §Perf).

Covers the encoding from four angles so it cannot drift silently:
  * a golden-format regression (hand-computed word constants);
  * pack/decode roundtrip property tests (hypothesis) in both plane
    regimes, including the shared-field validation errors;
  * all-three-executor parity on suite matrices in the 1-plane regime and
    the forced 2-plane large-n fallback;
  * all-NOP stall-row elision: hardware vs emitted cycle accounting and
    executor parity on a psum-starved DAG that provokes global stalls.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import api, executor
from repro.core.csr import random_rhs, serial_solve
from repro.core.matrices import generate
from repro.core.program import (
    CTL_BITS,
    OP_BITS,
    OP_FINAL,
    SLOT_BITS,
    SRC_BITS,
    AccelConfig,
    decode_instructions,
    pack_instructions,
    packed_planes,
    validate_fields,
)
from repro.core.compiler import compile_program


def _fields(op, src, ctl, slot):
    """Wrap scalars into the [T=1, P=1] arrays pack_instructions expects."""
    return (np.array([[op]]), np.array([[src]]),
            np.array([[ctl]]), np.array([[slot]]))


# ------------------------------------------------------------- golden format
def test_golden_single_plane_word():
    """The exact bit layout is load-bearing (kernels decode it bitwise) —
    pin it with hand-computed constants."""
    assert (SRC_BITS, OP_BITS, CTL_BITS, SLOT_BITS) == (18, 2, 3, 8)
    word = pack_instructions(*_fields(2, 5, 3, 7), planes=1)
    assert word.shape == (1, 1, 1) and word.dtype == np.int32
    #        src 5   | op 2 << 18 | ctl 3 << 20 | slot 7 << 23
    assert int(word[0, 0, 0]) == 5 + (2 << 18) + (3 << 20) + (7 << 23)
    assert int(word[0, 0, 0]) == 62390277
    # the all-NOP lane is the zero word
    assert int(pack_instructions(*_fields(0, 0, 0, 0), planes=1)[0, 0, 0]) == 0
    # max-value fields still fit the non-negative int32 range
    wmax = pack_instructions(
        *_fields(3, (1 << SRC_BITS) - 1, 7, 255), planes=1)
    assert int(wmax[0, 0, 0]) == (1 << 31) - 1


def test_golden_two_plane_words():
    words = pack_instructions(*_fields(2, 300000, 3, 7), planes=2)
    assert words.shape == (1, 2, 1) and words.dtype == np.int32
    assert int(words[0, 0, 0]) == 300000            # plane 0: full-width src
    assert int(words[0, 1, 0]) == 2 + (3 << 2) + (7 << 5) == 238


def test_packed_planes_threshold():
    assert packed_planes(1 << SRC_BITS) == 1        # n = 2^18 still fits
    assert packed_planes((1 << SRC_BITS) + 1) == 2  # one row more -> fallback
    assert packed_planes(64) == 1


def test_program_golden_format():
    """A compiled Program's packed tensor is self-consistent: decode ->
    re-pack reproduces it bit-exactly, and out_idx is derived from (op, src)."""
    prog = api.compile(generate("band_cz"))
    assert prog.instr.dtype == np.int32
    assert prog.instr.shape == (prog.cycles, 1, prog.num_cus)
    op, src, ctl, slot = decode_instructions(prog.instr, prog.planes)
    repacked = pack_instructions(op, src, ctl, slot, planes=prog.planes)
    np.testing.assert_array_equal(repacked, prog.instr)
    np.testing.assert_array_equal(
        prog.out_idx, np.where(op == OP_FINAL, src, prog.n))
    # every emitted row has at least one active lane (stall rows elided)
    assert (op != 0).any(axis=1).all()


# ---------------------------------------------------- roundtrip (seeded sweep)
# (the hypothesis property variant lives in test_packed_property.py,
# importorskip-guarded; this seeded sweep always runs in tier-1)
@pytest.mark.parametrize("planes", [1, 2])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_pack_decode_roundtrip_seeded(planes, seed):
    rng = np.random.default_rng(seed)
    shape = (int(rng.integers(1, 7)), int(rng.integers(1, 9)))
    src_hi = (1 << SRC_BITS) - 1 if planes == 1 else (1 << 30)
    op = rng.integers(0, 4, shape)
    src = rng.integers(0, src_hi + 1, shape)
    ctl = rng.integers(0, 8, shape)
    slot = rng.integers(0, 256, shape)
    words = pack_instructions(op, src, ctl, slot, planes=planes)
    assert words.dtype == np.int32 and words.shape[1] == planes
    op2, src2, ctl2, slot2 = decode_instructions(words, planes)
    np.testing.assert_array_equal(op2, op)
    np.testing.assert_array_equal(src2, src)
    np.testing.assert_array_equal(ctl2, ctl)
    np.testing.assert_array_equal(slot2, slot)


def test_decode_matches_on_jax_arrays():
    """The shared decode helper is backend-agnostic: jnp arrays decode to
    the same fields the numpy path produces."""
    import jax.numpy as jnp

    prog = api.compile(generate("wide_c36"))
    ref = decode_instructions(prog.instr, prog.planes)
    jx = decode_instructions(jnp.asarray(prog.instr), prog.planes)
    for a, b in zip(ref, jx):
        np.testing.assert_array_equal(np.asarray(b), a)


# ----------------------------------------------------------------- validation
@pytest.mark.parametrize("bad,match", [
    (dict(op=4), "op"),
    (dict(ctl=8), "ctl"),
    (dict(slot=256), "slot"),
    (dict(src=1 << SRC_BITS), "src"),
    (dict(src=-1), "src"),
])
def test_field_validation_rejects_overflow(bad, match):
    """The single shared validation point (satellite: the slot field could
    silently overflow 8 bits via schedule's overflow-slot growth)."""
    base = dict(op=1, src=3, ctl=2, slot=5)
    base.update(bad)
    with pytest.raises(ValueError, match=match):
        pack_instructions(
            *_fields(base["op"], base["src"], base["ctl"], base["slot"]),
            planes=1)


def test_validate_fields_two_plane_src_unbounded():
    # plane-2 src is full int32; only the control fields are width-checked
    validate_fields(*_fields(1, 1 << 25, 2, 5), planes=2)
    with pytest.raises(ValueError, match="slot"):
        validate_fields(*_fields(1, 1 << 25, 2, 300), planes=2)


# ------------------------------------------------------------ executor parity
def _parity(prog, mat, seed, impls=("numpy", "jax", "pallas")):
    b = random_rhs(mat, seed)
    ref = serial_solve(mat, b)
    if "numpy" in impls:
        np.testing.assert_allclose(api.solve_numpy(prog, b), ref,
                                   rtol=1e-5, atol=1e-5 * np.abs(ref).max())
    if "jax" in impls:
        np.testing.assert_allclose(api.solve(prog, b), ref,
                                   rtol=1e-5, atol=1e-5 * np.abs(ref).max())
    if "pallas" in impls:
        from repro.kernels.sptrsv import ops

        np.testing.assert_allclose(ops.solve(prog, b, interpret=True), ref,
                                   rtol=1e-5, atol=1e-5 * np.abs(ref).max())


@pytest.mark.parametrize("name", ["band_cz", "ckt_rajat04", "hub_small"])
@pytest.mark.parametrize("planes", [1, 2])
def test_all_executors_parity_both_regimes(name, planes):
    """Suite parity in the packed 1-plane regime AND the forced 2-plane
    large-n fallback (n >= 2^18 triggers it for real; forcing keeps the
    test matrix compile-time small)."""
    mat = generate(name)
    prog = compile_program(mat, planes=planes)
    assert prog.planes == planes
    assert prog.instr_bytes_per_lane_cycle() == 4 * planes + 4
    _parity(prog, mat, seed=17 + planes)


def test_two_plane_blocked_placement_parity():
    mat = generate("band_cz")
    prog = compile_program(mat, planes=2)
    from repro.kernels.sptrsv import ops

    b = random_rhs(mat, 23)
    x = ops.solve(prog, b, cycles_per_block=64, interpret=True,
                  placement="blocked")
    ref = serial_solve(mat, b)
    np.testing.assert_allclose(x, ref, rtol=1e-5,
                               atol=1e-5 * np.abs(ref).max())


# ------------------------------------------------------------- stall elision
def test_stall_rows_elided_with_parity():
    """A psum-starved config provokes global stalls (all lanes blocked);
    those all-NOP rows must be counted as hardware cycles but elided from
    the emitted stream — and every executor must still match the oracle."""
    mat = generate("ckt_rajat04")
    prog = compile_program(mat, AccelConfig(psum_words=2))
    st_ = prog.stats
    assert st_.emitted_cycles < st_.cycles, "config did not provoke stalls"
    assert prog.cycles == st_.emitted_cycles
    assert prog.row_lo.shape == (prog.cycles,)
    # elided rows carried no work: per-op totals are unchanged
    assert (prog.opcode == 1).sum() == st_.exec_edges
    assert (prog.opcode == 2).sum() == st_.exec_finals
    _parity(prog, mat, seed=31)


def test_hardware_cycle_count_unchanged_by_elision():
    """stats.cycles is the paper's hardware metric: a serial chain still
    costs exactly 2n-1 cycles regardless of emission policy."""
    mat = generate("chain_1k")
    prog = api.compile(mat)
    assert prog.stats.cycles == 2 * mat.n - 1
    assert prog.stats.emitted_cycles <= prog.stats.cycles


# ------------------------------------------------- traffic accounting + smoke
def test_instr_bytes_accounting():
    prog = api.compile(generate("band_cz"))
    assert prog.instr_bytes_per_lane_cycle() == 8   # was 24 unpacked
    assert prog.instr_bytes() == prog.cycles * prog.num_cus * 8


def test_vmem_instruction_buffers_halved():
    """Acceptance: the Pallas double-buffer footprint must be at least
    halved by the packed encoding (it is 3x smaller: 8 vs 24 B)."""
    from repro.kernels.sptrsv import ops

    from repro.kernels import common
    from repro.kernels.sptrsv import kernel

    prog = api.compile(generate("band_cz"))
    # the lane-state stream (blocked kernel) streams per-lane words
    now = ops.instr_buffer_bytes(prog, 128, "blocked")
    five_plane = 2 * 128 * prog.num_cus * 24
    assert now * 2 <= five_plane
    # the instruction buffers live in SMEM; the resident kernel's VMEM is
    # its x buffer alone, the blocked kernel's adds the lane state
    acct = ops.state_bytes(prog, 8, placement="resident")
    assert acct["instr"] == ops.instr_buffer_bytes(prog, 128)
    assert acct["vmem"] == acct["xb"]
    plan = ops.plan_window(prog, 64)
    acct_b = ops.state_bytes(prog, 8, placement="blocked", plan=plan,
                             cycles_per_block=64)
    assert acct_b["xb"] == plan.state_bytes(8)
    assert acct_b["instr"] == ops.instr_buffer_bytes(prog, 64, "blocked")
    lanes = kernel.lane_rows(prog.num_cus, executor._psum_slots(prog))
    assert acct_b["vmem"] - acct_b["xb"] == common.tiled_bytes(lanes, 8)


def test_instruction_breakdown_smoke():
    """Tier-1 guard on the traffic accounting (satellite: regressions must
    fail the fast suite, not just benchmark runs)."""
    from benchmarks.instruction_breakdown import run

    rows = run(smoke=True)
    assert rows, "smoke set is empty"
    for r in rows:
        assert r["bytes_per_lane_cycle"] <= 8, r
        assert r["traffic_ratio"] >= 3.0, r
        assert r["emitted_cycles"] <= r["cycles"], r
