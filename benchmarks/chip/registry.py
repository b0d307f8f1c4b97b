"""Find the benchmark's pieces by name.

Everything that belongs to one configuration, one traffic mix, one traffic
driver, one generator or one metric is a file of its own under this
directory, named after it:

    configs/<config>.json      sizes, generator, source, fingerprint, limit
    generators/<name>.py       ``generate(**params) -> (rows, cols, vals, diag)``
    traffic/<mix>.json         ``{"driver": ..., <parameters>}``
    drivers/<driver>.py        the loop that offers a mix to the system
    metrics/<metric>.py        ``read(ctx) -> float | None``

A cell is an entry of ``workloads`` in ``BENCHMARK.json`` (configuration,
traffic mix, chips); its metrics are the ``end_to_end`` and ``per_layer``
entries whose ``workloads`` list names it, or that have no such list.
Adding a cell, mix or metric adds files and entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownName(LookupError):
    """A cell, configuration, mix, driver, generator or metric that has no
    entry or no file."""


def _path(kind: str, name: str, suffix: str) -> Path:
    if not _NAME.match(name):
        raise UnknownName(f"{kind}: {name!r} is not a valid name")
    path = HERE / kind / f"{name}{suffix}"
    if not path.is_file():
        raise UnknownName(f"{kind}: no file {path.relative_to(HERE)}")
    return path


def load_json(kind: str, name: str) -> dict:
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def load_code(kind: str, name: str):
    """Import ``<kind>/<name>.py`` (names may hold dots, so by path)."""
    path = _path(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(path: Path | None = None) -> dict:
    with open(path or CHECKOUT / "BENCHMARK.json") as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple   # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None) -> Cell:
    """Resolve a cell of ``BENCHMARK.json`` with its configuration and mix."""
    bench = bench if bench is not None else benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise UnknownName(f"workload {name!r} is not in BENCHMARK.json "
                          f"({', '.join(w['name'] for w in bench['workloads'])})")
    config = dict(load_json("configs", entry["config"]), name=entry["config"])
    traffic = dict(load_json("traffic", entry["traffic"]),
                   name=entry["traffic"])
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic,
                end_to_end=tuple(m for m in bench["end_to_end"]
                                 if _applies(m, name)),
                per_layer=tuple(m for m in bench["per_layer"]
                                if _applies(m, name)))
