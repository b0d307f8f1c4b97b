"""The benchmark's pieces load by name, and BENCHMARK.json keeps to the
shape the harness reads."""

import re

import pytest

from benchmarks.chip import registry

BENCH = registry.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = registry.cell(name, BENCH)
    assert cell.chips in (1, 4)
    assert cell.config["fingerprint"]["n"] == cell.config["n"]
    registry.load_code("generators", cell.config["generator"])
    registry.load_code("drivers", cell.traffic["driver"])
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    # every per-layer metric a cell reports moves an end-to-end metric
    # that the same cell reports
    for m in cell.per_layer:
        assert m["moves"] in names, (name, m["name"])


@pytest.mark.parametrize("metric", sorted(
    {p.stem for p in (registry.HERE / "metrics").glob("*.py")}
    | {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}))
def test_metric_reader_loads(metric):
    assert callable(registry.load_code("metrics", metric).read)


@pytest.mark.parametrize("kind,name", [
    ("configs", "no_such_config"), ("traffic", "no_such_mix"),
    ("metrics", "no_such_metric"), ("drivers", "no_such_driver"),
    ("generators", "../registry"), ("configs", "a b"),
])
def test_unknown_name_refused(kind, name):
    with pytest.raises(registry.UnknownName):
        if kind in ("configs", "traffic"):
            registry.load_json(kind, name)
        else:
            registry.load_code(kind, name)


def test_unknown_cell_refused():
    with pytest.raises(registry.UnknownName):
        registry.cell("no_such.cell", BENCH)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    all_names = ([c["name"] for c in BENCH["configs"]] + CELLS
                 + [m["name"] for m in BENCH["end_to_end"]
                    + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in all_names)
    configs = {c["name"] for c in BENCH["configs"]}
    assert configs == {w["config"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert registry.load_json("configs", c["name"])["reduced"] == \
            c["reduced"]
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
