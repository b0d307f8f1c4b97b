"""Fig. 9d/e/f: ICR effect on bank constraints, conflicts, and data reuse."""

from __future__ import annotations

import dataclasses

from repro.core.matrices import generate
from repro.core.program import AccelConfig
from repro.core.compiler import compile_program

from .common import FIG9_SET, emit


def run() -> list[dict]:
    rows = []
    for name in FIG9_SET:
        mat = generate(name)
        on = compile_program(mat, AccelConfig(icr=True)).stats
        off = compile_program(mat, AccelConfig(icr=False)).stats
        rows.append({
            "name": name,
            "constraints_icr": on.constraints,
            "constraints_noicr": off.constraints,
            "conflicts_icr": on.conflicts,
            "conflicts_noicr": off.conflicts,
            "reuse_icr": on.reuse_events,
            "reuse_noicr": off.reuse_events,
            "cycles_icr": on.cycles,
            "cycles_noicr": off.cycles,
        })
    return rows


def main() -> None:
    emit(run(), "fig9def_icr_ablation")


if __name__ == "__main__":
    main()
