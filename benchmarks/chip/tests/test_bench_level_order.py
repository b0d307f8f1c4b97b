"""The row-order pass reader returns the seconds of ``level_order`` from the
program's ``pass_stats``, and nothing where the program records none (a
program compiled before the pass existed)."""

import types

import pytest

from benchmarks.chip import registry


def _ctx(pass_stats):
    return {"program_stats": types.SimpleNamespace(pass_stats=pass_stats)}


def _read(pass_stats):
    return registry.load_code("metrics", "host_level_order_s").read(
        _ctx(pass_stats))


def test_reader_returns_the_pass_seconds():
    stats = [types.SimpleNamespace(name=name, seconds=s, metrics={})
             for name, s in (("level_order", 0.75), ("partition", 0.5),
                             ("psum_schedule", 7.0), ("icr_reorder", 11.5))]
    assert _read(stats) == 0.75


@pytest.mark.parametrize("pass_stats", [None, [], [
    types.SimpleNamespace(name="partition", seconds=0.5, metrics={})]])
def test_reader_without_the_pass_reads_nothing(pass_stats):
    assert _read(pass_stats) is None


def test_program_records_the_pass(monkeypatch):
    """The program's own compile of the configuration's generator at a
    test size, with the level order forced (the pass picks it on its own
    only past a core's VMEM): the reader finds the pass it records."""
    from repro.core import api
    from repro.core.compiler import order
    from repro.core.csr import from_coo

    monkeypatch.setattr(order, "wants_level", lambda n: True)
    gen = registry.load_code("generators", "lap7_ic0")
    mat = from_coo(512, *gen.generate(8, 512), name="lap7_ic0_8")
    prog = api.compile(mat)
    assert _read(prog.stats.pass_stats) >= 0.0
    by = {p.name: p for p in prog.stats.pass_stats}
    assert by["level_order"].metrics["reordered"] is True
