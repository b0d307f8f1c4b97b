#!/usr/bin/env python
"""Bench-trajectory schema check: the perf history stays machine-readable.

The repo keeps three perf *trajectory* files — ``BENCH_serve.json``
(appended by ``benchmarks/serve_load.py --record``),
``BENCH_serve_chaos.json`` (appended by ``benchmarks/serve_chaos.py
--record``) and ``BENCH_schedule.json`` (appended by
``benchmarks/schedule_frontier.py --record``) — so re-anchors can read a
curve instead of a single CSV snapshot.  A trajectory is only useful if
every entry still parses years later, so this check pins the schemas:
top-level envelope, per-entry metadata, and the per-row fields with
their types.  Runs standalone (``python scripts/check_bench.py``) and as
tier-1 tests (`tests/test_serve.py`, `tests/test_resilience.py`,
`tests/test_strategies.py`).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
BENCH_JSON = REPO / "BENCH_serve.json"
CHAOS_JSON = REPO / "BENCH_serve_chaos.json"
SCHEDULE_JSON = REPO / "BENCH_schedule.json"

SCHEMA = "sptrsv-bench-serve"
VERSION = 1
CHAOS_SCHEMA = "sptrsv-bench-serve-chaos"
CHAOS_VERSION = 1
SCHEDULE_SCHEMA = "sptrsv-bench-schedule"
SCHEDULE_VERSION = 1

# required per-row fields -> accepted types
ROW_FIELDS = {
    "name": str,
    "n": int,
    "requests": int,
    "offered_batch": int,
    "batched_solves_per_s": (int, float),
    "sequential_solves_per_s": (int, float),
    "speedup": (int, float),
    "p50_ms": (int, float),
    "p99_ms": (int, float),
}
ENTRY_FIELDS = {
    "recorded": str,   # ISO date, checked below
    "label": str,
    "host": str,
    "offered_batch": int,
    "rows": list,
}

CHAOS_ROW_FIELDS = {
    "fault": str,
    "requests": int,
    "goodput": (int, float),
    "completed": int,
    "failed_typed": int,
    "shed": int,
    "silent_wrong": int,
    "p50_virtual_ms": (int, float),
    "p99_virtual_ms": (int, float),
    "retries": int,
    "degraded_flushes": int,
    "incidents": int,
}
CHAOS_ENTRY_FIELDS = {
    "recorded": str,
    "label": str,
    "host": str,
    "seed": int,
    "overhead_pct": (int, float),
    "rows": list,
}

# scheduling-strategy frontier (benchmarks/schedule_frontier.py): one
# cycles/stalls/spills triple per registered strategy, plus auto's pick
_STRATEGY_NAMES = ("paper", "locality", "cpath", "eager")
SCHEDULE_ROW_FIELDS = {
    "name": str,
    "n": int,
    "nnz": int,
    "auto_pick": str,
    "auto_cycles": int,
    "auto_win": int,
    **{f"{s}_{m}": int for s in _STRATEGY_NAMES
       for m in ("cycles", "stalls", "spills")},
}
SCHEDULE_ENTRY_FIELDS = {
    "recorded": str,
    "label": str,
    "host": str,
    "wins": int,
    "rows": list,
}


def _check_file(path: Path, schema: str, version: int, entry_fields: dict,
                row_fields: dict, creator: str) -> list[str]:
    """Validate one trajectory file; returns human-readable problems."""
    if not path.exists():
        return [f"{path.name} missing (run {creator} --record to create it)"]
    try:
        doc = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        return [f"{path.name}: not valid JSON ({e})"]
    problems: list[str] = []
    if doc.get("schema") != schema:
        problems.append(f"{path.name}: schema must be {schema!r}, "
                        f"got {doc.get('schema')!r}")
    if doc.get("version") != version:
        problems.append(f"{path.name}: version must be {version}, "
                        f"got {doc.get('version')!r}")
    entries = doc.get("entries")
    if not isinstance(entries, list) or not entries:
        return problems + [f"{path.name}: entries must be a non-empty list"]
    for i, entry in enumerate(entries):
        where = f"{path.name}:entries[{i}]"
        for field, typ in entry_fields.items():
            if not isinstance(entry.get(field), typ):
                problems.append(f"{where}.{field}: expected {typ}, "
                                f"got {entry.get(field)!r}")
        rec = entry.get("recorded", "")
        if isinstance(rec, str) and (len(rec) != 10 or rec[4] != "-"
                                     or rec[7] != "-"):
            problems.append(f"{where}.recorded: expected YYYY-MM-DD, "
                            f"got {rec!r}")
        rows = entry.get("rows") or []
        if isinstance(rows, list) and not rows:
            problems.append(f"{where}.rows: empty")
        for j, row in enumerate(rows if isinstance(rows, list) else []):
            for field, typ in row_fields.items():
                if not isinstance(row.get(field), typ) or \
                        isinstance(row.get(field), bool):
                    problems.append(
                        f"{where}.rows[{j}].{field}: expected {typ}, "
                        f"got {row.get(field)!r}")
    return problems


def check(path: Path = BENCH_JSON) -> list[str]:
    """Validate the serve-load trajectory (empty == clean)."""
    return _check_file(path, SCHEMA, VERSION, ENTRY_FIELDS, ROW_FIELDS,
                       "benchmarks/serve_load.py")


def check_chaos(path: Path = CHAOS_JSON) -> list[str]:
    """Validate the serve-chaos trajectory (empty == clean)."""
    return _check_file(path, CHAOS_SCHEMA, CHAOS_VERSION, CHAOS_ENTRY_FIELDS,
                       CHAOS_ROW_FIELDS, "benchmarks/serve_chaos.py")


def check_schedule(path: Path = SCHEDULE_JSON) -> list[str]:
    """Validate the schedule-frontier trajectory (empty == clean)."""
    problems = _check_file(path, SCHEDULE_SCHEMA, SCHEDULE_VERSION,
                           SCHEDULE_ENTRY_FIELDS, SCHEDULE_ROW_FIELDS,
                           "benchmarks/schedule_frontier.py")
    if problems:
        return problems
    # the frontier invariant the trajectory exists to witness: auto is
    # never worse than the paper baseline, and each win is strict
    doc = json.loads(path.read_text())
    for i, entry in enumerate(doc["entries"]):
        for j, row in enumerate(entry["rows"]):
            where = f"{path.name}:entries[{i}].rows[{j}]"
            if row["auto_cycles"] > row["paper_cycles"]:
                problems.append(f"{where}: auto_cycles "
                                f"{row['auto_cycles']} worse than paper "
                                f"{row['paper_cycles']}")
            if row["auto_win"] != int(row["auto_cycles"]
                                      < row["paper_cycles"]):
                problems.append(f"{where}: auto_win flag inconsistent "
                                f"with the cycle counts")
    return problems


def main() -> int:
    problems = check() + check_chaos() + check_schedule()
    for p in problems:
        print(f"check_bench: {p}", file=sys.stderr)
    if problems:
        print(f"check_bench: {len(problems)} schema problem(s)",
              file=sys.stderr)
        return 1
    for path in (BENCH_JSON, CHAOS_JSON, SCHEDULE_JSON):
        doc = json.loads(path.read_text())
        n_rows = sum(len(e["rows"]) for e in doc["entries"])
        print(f"check_bench: {path.name} OK ({len(doc['entries'])} "
              f"trajectory entr{'y' if len(doc['entries']) == 1 else 'ies'}, "
              f"{n_rows} rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
