"""Fig. 9b/c: total + blocking cycles vs psum register-file capacity."""

from __future__ import annotations

import dataclasses

from repro.core.matrices import generate
from repro.core.program import AccelConfig
from repro.core.compiler import compile_program

from .common import emit

MATRICES = ["ckt_rajat04", "ckt_add20", "band_dw2048", "chem_bp",
            "grid_activsg", "wide_c36", "ckt_rajat19", "hub_small"]
CAPACITIES = [0, 1, 2, 4, 8, 16]


def run() -> list[dict]:
    rows = []
    for name in MATRICES:
        mat = generate(name)
        base = None
        for cap in CAPACITIES:
            cfg = AccelConfig(psum_words=max(cap, 1), psum_cache=cap > 0)
            st = compile_program(mat, cfg).stats
            blocking = st.dnop + st.pnop + st.bnop + st.snop
            if base is None:
                base = (st.cycles, max(blocking, 1))
            rows.append({
                "name": name,
                "psum_words": cap,
                "cycles": st.cycles,
                "cycles_norm": round(st.cycles / base[0], 4),
                "blocking": blocking,
                "blocking_norm": round(blocking / base[1], 4),
                "pnop": st.pnop,
                "dm_escapes": st.dm_escapes,
            })
    return rows


def main() -> None:
    emit(run(), "fig9bc_psum_sweep")


if __name__ == "__main__":
    main()
