"""The compiler-pass readers return the seconds of their pass from the
program's ``pass_stats``, and nothing where the program records none."""

import types

import pytest

from benchmarks.chip import registry

PASSES = {"host_icr_reorder_s": "icr_reorder",
          "host_psum_schedule_s": "psum_schedule"}


def _ctx(pass_stats):
    return {"program_stats": types.SimpleNamespace(pass_stats=pass_stats)}


@pytest.mark.parametrize("metric", sorted(PASSES))
def test_reader_returns_its_pass_seconds(metric):
    stats = [types.SimpleNamespace(name=name, seconds=s, metrics={})
             for name, s in (("partition", 0.5), ("cu_assign", 1.25),
                             ("psum_schedule", 7.0), ("icr_reorder", 11.5),
                             ("stall_elide", 0.25), ("pack_emit", 2.0))]
    want = {"icr_reorder": 11.5, "psum_schedule": 7.0}[PASSES[metric]]
    assert registry.load_code("metrics", metric).read(_ctx(stats)) == want


@pytest.mark.parametrize("metric", sorted(PASSES))
@pytest.mark.parametrize("pass_stats", [None, []])
def test_reader_without_the_pass_reads_nothing(metric, pass_stats):
    assert registry.load_code("metrics", metric).read(_ctx(pass_stats)) is None
