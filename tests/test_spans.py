"""Profiler spans of the solve path: a few `api.solve_batch` calls under
`jax.profiler`, read back from the xplane the profiler writes."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import api, shard
from repro.core.matrices import generate

ROOT = "sptrsv.solve_batch"
BUILD = "sptrsv.executor_build"
STEPS = ["sptrsv.stage_in", "sptrsv.dispatch", "sptrsv.readback"]
# widths 1 and 3 (padded to 1 and 8): the first call of each builds; the
# interpreted kernel fills a profile fast, so it makes two calls
WIDTHS = {"jax": [1, 1, 3, 3, 1], "pallas": [1, 1], "sharded": [1, 1, 3, 3, 1]}


def _program_spans(log_dir) -> list:
    """``(start_ns, end_ns, name)`` of every ``sptrsv.*`` host event."""
    path, = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sptrsv."):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    # a parent before the children that start with it
    return sorted(out, key=lambda s: (s[0], -s[1]))


@pytest.mark.parametrize("path", sorted(WIDTHS))
def test_solve_batch_spans(tmp_path, path):
    widths = WIDTHS[path]
    opts = {"pallas": {"backend": "pallas", "interpret": True},
            "sharded": {"mesh": shard.batch_mesh(1)}}.get(path, {})
    prog = api.compile(generate("ckt_fpga"))  # fresh: no executor cached
    rng = np.random.default_rng(7)
    bs = [rng.standard_normal((prog.n, w)).astype(np.float32)
          for w in widths]
    popts = jax.profiler.ProfileOptions()
    popts.host_tracer_level = 1  # user annotations, not the runtime's own
    popts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=popts)
    try:
        xs = [api.solve_batch(prog, b, **opts) for b in bs]
    finally:
        jax.profiler.stop_trace()
    for b, x in zip(bs, xs):
        assert x.shape == b.shape
    spans = _program_spans(tmp_path)
    roots = [s for s in spans if s[2] == ROOT]
    assert len(roots) == len(widths)
    nested = 0
    seen = set()
    for (lo, hi, _), width in zip(roots, widths):
        inner = [s for s in spans
                 if s[2] != ROOT and lo <= s[0] and s[1] <= hi]
        nested += len(inner)
        first = width not in seen
        seen.add(width)
        # the steps in call order, each once; a build only on a new width
        assert [s[2] for s in inner] == [BUILD] * first + STEPS
        for a, b in zip(inner, inner[1:]):
            assert a[1] <= b[0]
    assert nested == len(spans) - len(roots)  # nothing outside a call


def test_compile_pass_spans(tmp_path):
    """One compile records one ``sptrsv.compile.<pass>`` span per pass of
    `compiler.PASS_NAMES`, in that order; the ICR reorder runs once per
    hardware cycle inside the schedule pass, so its spans, one per cycle,
    all lie inside ``sptrsv.compile.psum_schedule``."""
    from repro.core import compiler

    mat = generate("hpcg_8")
    popts = jax.profiler.ProfileOptions()
    popts.host_tracer_level = 1
    popts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=popts)
    try:
        prog = api.compile(mat)
    finally:
        jax.profiler.stop_trace()
    spans = [s for s in _program_spans(tmp_path)
             if s[2].startswith("sptrsv.compile.")]
    names = [s[2].removeprefix("sptrsv.compile.") for s in spans]
    assert list(dict.fromkeys(names)) == list(compiler.PASS_NAMES)
    icr = [s for s in spans if s[2] == "sptrsv.compile.icr_reorder"]
    passes = [s for s in spans if s not in icr]
    assert [s[2] for s in passes] == [f"sptrsv.compile.{p}"
                                      for p in compiler.PASS_NAMES
                                      if p != "icr_reorder"]
    for a, b in zip(passes, passes[1:]):
        assert a[1] <= b[0]
    lo, hi, _ = passes[compiler.PASS_NAMES.index("psum_schedule")]
    assert len(icr) == prog.stats.cycles
    assert all(lo <= s[0] and s[1] <= hi for s in icr)
