#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 21,22,23 --seconds 2

One process compiles the cell once, then for each ``--seeds`` seed makes
that seed's inputs, drives the cell's traffic through the timed path for
``--seconds`` and compares every answer kept with the float64 reference:
the program's reading.  For each ``--control-seeds`` seed it puts the
control in the program's place, the same forward substitution rounded to
bfloat16 (the precision below the float32 the configurations state), on that
seed's inputs: the control's reading.  Prints one JSON line per reading
and, last, the largest program reading and the smallest control reading.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parents[2])

from benchmarks.chip import reference, registry, run  # noqa: E402


def control_inputs(kind, limit: int = 16):
    """Up to ``limit`` columns of the seed's inputs, as one [n, k] block."""
    import numpy as np

    cols = np.concatenate([np.asarray(b).reshape(kind.n, -1)
                           for b in kind.pool], axis=1)
    return cols[:, :limit]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    try:
        run.require_tpu(cell.chips)
    except run.NoChip as e:
        return int(e.code)
    run.enable_cache()
    spans: dict = {}
    mat, ref = run.build_matrix(cell.config)
    kind = run.DRIVERS[cell.traffic["driver"]](cell, mat, spans)
    program, control = [], []
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        kind.prepare(seed, spans)
        res = kind.window(args.seconds)
        pairs = kind.answers(res)
        err = run.compare(ref, pairs)
        program.append(err)
        print(json.dumps({"seed": seed, "side": "program", "max_rel_err": err,
                          "answers": len(pairs), "failed": res["failed"]}),
              flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        kind.prepare(seed, spans)
        b = control_inputs(kind)
        t = time.perf_counter()
        err = reference.rel_err(ref.solve_lowp(b, "bfloat16"), ref.solve(b))
        control.append(err)
        print(json.dumps({"seed": seed, "side": "control bfloat16",
                          "max_rel_err": err, "columns": b.shape[1],
                          "seconds": time.perf_counter() - t}), flush=True)
    print(json.dumps({"workload": cell.name,
                      "program_max": max(program) if program else None,
                      "control_min": min(control) if control else None,
                      "limit": cell.config["limit_max_rel_err"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
