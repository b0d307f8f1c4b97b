"""Scheduling-strategy frontier: per-matrix cycles across every strategy.

    PYTHONPATH=src python -m benchmarks.schedule_frontier            # CSV
    PYTHONPATH=src python -m benchmarks.schedule_frontier --record   # + JSON
    PYTHONPATH=src python -m benchmarks.schedule_frontier --smoke    # tier-1

Compiles every suite matrix with ``schedule="auto"`` (DESIGN.md §11): the
compiler runs each registered strategy — the paper's psum-cache scheduler
and the list-scheduler pick orders of the same machine — scores each dense
trace with the analytic cost model, and keeps the predicted-cheapest.
Because the cost model's cycle count is exact (it *is* the dense trace
length), the recorded frontier doubles as the measured one: per matrix
the row carries every strategy's cycles / stall rows / psum spills, the
strategy auto picked, its measured ``stats.cycles``, and whether that
strictly beat the paper baseline.

``--record`` appends a dated entry to the ``BENCH_schedule.json``
trajectory file (schema checked by ``scripts/check_bench.py``).
``--smoke`` (wired into tier-1 via `tests/test_strategies.py`) runs a
small subset and asserts auto is never worse than the paper schedule and
wins where the frontier says it must.
"""

from __future__ import annotations

import argparse
import json
import os
import time

from repro.core import api
from repro.core.matrices import generate, suite_names

from .common import emit

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_schedule.json")
BENCH_SCHEMA = "sptrsv-bench-schedule"
BENCH_VERSION = 1

STRATEGY_NAMES = ("paper", "locality", "cpath", "eager")
# ckt_fpga must be an auto win (list schedulers beat the paper's resume
# order on psum-bound circuit DAGs); band_cz is an order-forced tie.
SMOKE_SET = ("band_cz", "ckt_fpga")


def bench_matrix(name: str) -> dict:
    """One frontier row: every strategy's predicted cost + auto's pick."""
    mat = generate(name)
    prog = api.compile(mat, schedule="auto")
    st = prog.stats
    costs = st.schedule_costs
    row: dict = {"name": name, "n": int(mat.n), "nnz": int(mat.nnz)}
    for s in STRATEGY_NAMES:
        c = costs[s]
        row[f"{s}_cycles"] = int(c["cycles"])
        row[f"{s}_stalls"] = int(c["stall_rows"])
        row[f"{s}_spills"] = int(c["psum_spills"])
    row["auto_pick"] = st.schedule
    row["auto_cycles"] = int(st.cycles)
    row["auto_win"] = int(st.cycles < costs["paper"]["cycles"])
    assert st.cycles == costs[st.schedule]["cycles"], (
        f"{name}: cost model diverged from measured cycles")
    assert st.cycles <= costs["paper"]["cycles"], (
        f"{name}: auto picked a schedule worse than the paper baseline")
    return row


def record_trajectory(rows: list[dict], label: str) -> None:
    """Append a dated entry to the BENCH_schedule.json trajectory file."""
    doc = {"schema": BENCH_SCHEMA, "version": BENCH_VERSION, "entries": []}
    if os.path.exists(BENCH_JSON):
        with open(BENCH_JSON) as f:
            doc = json.load(f)
    doc["entries"].append({
        "recorded": time.strftime("%Y-%m-%d"),
        "label": label,
        "host": "cpu-interpret" if not _on_tpu() else "tpu",
        "wins": sum(r["auto_win"] for r in rows),
        "rows": rows,
    })
    with open(BENCH_JSON, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# trajectory entry #{len(doc['entries'])} -> {BENCH_JSON}")


def _on_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def run(smoke: bool = False, max_n: int = 3000, names=None) -> list[dict]:
    names = names or (SMOKE_SET if smoke else suite_names(max_n=max_n))
    return [bench_matrix(n) for n in names]


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--record", action="store_true",
                    help="append results to BENCH_schedule.json")
    ap.add_argument("--label", default="schedule-frontier")
    ap.add_argument("--matrices", default="")
    ap.add_argument("--max-n", type=int, default=3000)
    args = ap.parse_args(argv)
    names = tuple(args.matrices.split(",")) if args.matrices else None
    rows = run(smoke=args.smoke, max_n=args.max_n, names=names)
    wins = sum(r["auto_win"] for r in rows)
    if args.smoke:
        assert any(r["auto_win"] for r in rows), (
            "smoke set contains no auto win — the frontier collapsed")
        print(f"# smoke: {len(rows)} matrices, auto never worse than "
              f"paper, {wins} strict win(s)")
        return
    emit(rows, "schedule_frontier")
    print(f"# auto strictly beats the paper schedule on {wins}/{len(rows)} "
          f"matrices (never worse on any; acceptance bar: >= 1/3)")
    if args.record:
        record_trajectory(rows, args.label)


if __name__ == "__main__":
    main()
