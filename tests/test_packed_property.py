"""Property-based tests (hypothesis) on the packed instruction encoding.

Complements `tests/test_packed.py` (which always runs): for ANY in-range
field arrays, pack -> decode must be the identity in both plane regimes,
and compiled programs must roundtrip bit-exactly.
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.program import (  # noqa: E402
    SRC_BITS,
    decode_instructions,
    pack_instructions,
)


@st.composite
def packed_fields(draw):
    planes = draw(st.sampled_from([1, 2]))
    t = draw(st.integers(min_value=1, max_value=8))
    p = draw(st.integers(min_value=1, max_value=16))
    shape = (t, p)
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    src_hi = (1 << SRC_BITS) - 1 if planes == 1 else np.iinfo(np.int32).max
    return planes, (
        rng.integers(0, 4, shape),
        rng.integers(0, int(src_hi) + 1, shape),
        rng.integers(0, 8, shape),
        rng.integers(0, 256, shape),
    )


@settings(max_examples=80, deadline=None)
@given(packed_fields())
def test_pack_decode_roundtrip(case):
    planes, (op, src, ctl, slot) = case
    words = pack_instructions(op, src, ctl, slot, planes=planes)
    assert words.dtype == np.int32 and words.shape[1] == planes
    op2, src2, ctl2, slot2 = decode_instructions(words, planes)
    np.testing.assert_array_equal(op2, op)
    np.testing.assert_array_equal(src2, src)
    np.testing.assert_array_equal(ctl2, ctl)
    np.testing.assert_array_equal(slot2, slot)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 60), st.integers(0, 2**31 - 1))
def test_random_program_repacks_bit_exactly(n, seed):
    """decode -> re-pack over a real compiled program is the identity."""
    from repro.core.csr import from_coo
    from repro.core.compiler import compile_program

    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for i in range(1, n):
        m = rng.random(i) < 0.3
        for j in np.nonzero(m)[0]:
            rows.append(i)
            cols.append(int(j))
    vals = rng.uniform(-1, 1, len(rows))
    diag = rng.uniform(1.0, 2.0, n)
    mat = from_coo(n, rows, cols, vals, diag, name=f"hyp_pack_{seed}")
    prog = compile_program(mat)
    fields = decode_instructions(prog.instr, prog.planes)
    np.testing.assert_array_equal(
        pack_instructions(*fields, planes=prog.planes), prog.instr)
