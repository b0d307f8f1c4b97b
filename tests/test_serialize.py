"""Versioned checksummed Program serialization (`core/serialize.py`).

Round-trip fidelity (arrays, config, stats, exact solve parity), the
corruption contract — *any* byte-level damage to a saved blob raises
`ProgramCorruptionError`, exercised both with targeted defects (magic,
version, truncation, trailing bytes) and hypothesis-driven random k-byte
corruption — and the `api.save_program`/`load_program` surface including
the load-time structural verify (DESIGN.md §7).
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import api, serialize
from repro.core.csr import from_coo, random_rhs
from repro.core.errors import ProgramCorruptionError
from repro.core.matrices import generate
from repro.core.program import ScheduleStats
from repro.core.robust import FaultInjector


def tiny_matrix(n: int = 24, seed: int = 3):
    """A small random lower-tri system — keeps blobs byte-cheap."""
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for i in range(1, n):
        for j in rng.choice(i, size=min(i, int(rng.integers(1, 4))),
                            replace=False):
            rows.append(i), cols.append(int(j))
    vals = rng.standard_normal(len(rows)) * 0.3
    diag = rng.standard_normal(n) + 4.0
    return from_coo(n, rows, cols, vals, diag, name=f"tiny{n}")


@pytest.fixture(scope="module")
def prog():
    return api.compile(generate("band_cz"))


# ------------------------------------------------------------- round trip
def test_roundtrip_bit_exact(prog, tmp_path):
    path = tmp_path / "band_cz.prog"
    api.save_program(prog, path)
    p2 = api.load_program(path)
    for name in ("instr", "val_idx", "stream"):
        np.testing.assert_array_equal(getattr(prog, name), getattr(p2, name))
    assert p2.config == prog.config
    assert (p2.n, p2.num_slots) == (prog.n, prog.num_slots)
    assert p2.content_crc32() == prog.content_crc32()
    for f in dataclasses.fields(ScheduleStats):
        if f.name in ("per_cu_edges", "pass_stats"):
            continue
        assert getattr(p2.stats, f.name) == getattr(prog.stats, f.name), f.name
    np.testing.assert_array_equal(p2.stats.per_cu_edges,
                                  prog.stats.per_cu_edges)
    assert p2.stats.pass_stats is None  # compile-run telemetry, not artifact
    b = random_rhs(generate("band_cz"), seed=1)
    np.testing.assert_array_equal(api.solve_numpy(prog, b),
                                  api.solve_numpy(p2, b))


# ------------------------------------------------------------- targeted defects
def test_bad_magic_version_truncation(prog):
    blob = serialize.dumps_program(prog)
    with pytest.raises(ProgramCorruptionError, match="magic"):
        serialize.loads_program(b"NOTPROG!" + blob[8:])
    bad_ver = blob[:8] + (99).to_bytes(4, "little") + blob[12:]
    with pytest.raises(ProgramCorruptionError, match="version"):
        serialize.loads_program(bad_ver)
    with pytest.raises(ProgramCorruptionError, match="truncated"):
        serialize.loads_program(blob[:10])
    with pytest.raises(ProgramCorruptionError, match="truncated|length"):
        serialize.loads_program(blob[:len(blob) // 2])
    with pytest.raises(ProgramCorruptionError, match="length"):
        serialize.loads_program(blob + b"\x00")


def test_corruption_is_a_valueerror(prog):
    """Taxonomy leaves keep the historical builtin for old callers."""
    blob = serialize.dumps_program(prog)
    with pytest.raises(ValueError):
        serialize.loads_program(blob[:10])


def test_load_verifies_structure(tmp_path):
    """CRC-clean but structurally corrupt content is stopped at load."""
    mat = tiny_matrix()
    prog = api.compile(mat)
    bad = FaultInjector(5).corrupt_stream(prog, k=1, mode="nan")
    path = tmp_path / "bad.prog"
    serialize.save_program(bad, path)  # checksums computed over bad bytes
    with pytest.raises(ProgramCorruptionError, match="non-finite"):
        api.load_program(path)
    p2 = api.load_program(path, verify=False)  # opt-out parses fine
    assert np.isnan(p2.stream).any()


# ------------------------------------------------------------- random corruption
_TINY = api.compile(tiny_matrix())
_BLOB = serialize.dumps_program(_TINY)


def _flip_k_bytes(blob: bytes, k: int, seed: int) -> bytes:
    rng = np.random.default_rng(seed)
    buf = bytearray(blob)
    for i in rng.integers(len(buf), size=k):
        buf[int(i)] ^= int(rng.integers(1, 256))
    return bytes(buf)


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("seed", range(12))
def test_any_byte_corruption_detected(k, seed):
    """save -> flip k random bytes -> load raises ProgramCorruptionError.

    Every byte of the format is covered by the header CRC or the payload
    CRC (or is the magic/version/CRC itself), so no corruption parses.
    Deterministic 60-case sweep; widened by hypothesis when available.
    """
    with pytest.raises(ProgramCorruptionError):
        serialize.loads_program(_flip_k_bytes(_BLOB, k, seed))


try:  # hypothesis is optional in this container — gate, don't require
    from hypothesis import given, settings, strategies as st
except ImportError:
    pass
else:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(1, 8), st.integers(0, 2**31 - 1))
    def test_any_byte_corruption_detected_hypothesis(k, seed):
        with pytest.raises(ProgramCorruptionError):
            serialize.loads_program(_flip_k_bytes(_BLOB, k, seed))
