"""Bit pins of the list-scheduler presets (``locality``, ``cpath``, ``eager``).

The paper preset is pinned against a frozen copy of the original compiler
(`tests/legacy_schedule.py`); the list presets have no such reference
beyond ``numpy == serial_solve``.  These pins fix each one's compiled
program on four matrices with the default `AccelConfig`: the CRC32 of
``instr``, ``val_idx`` and ``stream``, plus hardware and emitted cycles,
psum slots and x_i spills.  A change to the shared schedule machine
(`core/compiler/sched.py`) that moves any of their schedules fails here.

The fixture ``tests/schedule_presets.json`` is written by

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/test_schedule_presets.py --write

and is rewritten only by a change that means to alter a list schedule.
"""

import json
import sys
import zlib
from pathlib import Path

import numpy as np
import pytest

from repro.core import api
from repro.core.matrices import generate

FIXTURE = Path(__file__).with_name("schedule_presets.json")
PRESETS = ("locality", "cpath", "eager")
MATRICES = ("ckt_add20", "ckt_rajat04", "hub_wall", "band_cz")


def _crc(arr) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes())


def pin(matrix: str, preset: str) -> dict:
    prog = api.compile(generate(matrix), schedule=preset)
    return {
        "instr_crc32": _crc(prog.instr),
        "val_idx_crc32": _crc(prog.val_idx),
        "stream_crc32": _crc(prog.stream),
        "cycles": int(prog.stats.cycles),
        "emitted_cycles": int(prog.cycles),
        "num_slots": int(prog.num_slots),
        "spilled_values": int(prog.stats.spilled_values),
    }


@pytest.mark.parametrize("matrix", MATRICES)
@pytest.mark.parametrize("preset", PRESETS)
def test_list_preset_schedule_pinned(preset, matrix):
    want = json.loads(FIXTURE.read_text())[f"{preset}/{matrix}"]
    assert pin(matrix, preset) == want


if __name__ == "__main__":
    if sys.argv[1:] != ["--write"]:
        sys.exit(__doc__)
    pins = {f"{p}/{m}": pin(m, p) for p in PRESETS for m in MATRICES}
    FIXTURE.write_text(json.dumps(pins, indent=1, sort_keys=True) + "\n")
    print(f"wrote {len(pins)} pins -> {FIXTURE}")
