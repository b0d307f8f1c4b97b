"""`spans.py`: the program's host spans in a profile, on intervals made by
hand, on the traces recorded on a TPU v5e without program spans, and on
trimmed traces of 30 ckt_add20.b1 calls (B=1) from two runs with them."""

import glob
import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import spans, trace

FIXTURES = Path(__file__).parent / "fixtures"
UNSPANNED = ["ckt_add20.b1.trace.json", "band_huge64k.b16.trace.json"]
SPANNED = ["ckt_add20.b1.spans_a.trace.json",
           "ckt_add20.b1.spans_b.trace.json"]


def _load(name):
    with open(FIXTURES / name) as f:
        return json.load(f)


# one window [0, 200) and two calls; the first builds its executor
HAND = {
    "host": [["window", 0, 200], ["solve_batch", 10, 100],
             ["sptrsv.solve_batch", 12, 96],
             ["sptrsv.executor_build", 14, 6], ["sptrsv.stage_in", 20, 10],
             ["sptrsv.dispatch", 30, 10], ["sptrsv.readback", 40, 65],
             ["solve_batch", 120, 70], ["sptrsv.solve_batch", 121, 68],
             ["sptrsv.stage_in", 125, 5], ["sptrsv.dispatch", 130, 5],
             ["sptrsv.readback", 135, 53]],
    "devices": [{"name": "/device:TPU:0",
                 "ops": [["copy", 22, 2, False], ["k", 45, 50, True],
                         ["copy.1", 100, 4, False], ["k", 140, 40, True]]}],
}


@pytest.mark.parametrize("name", UNSPANNED)
def test_reduce_without_program_spans_is_pinned(name):
    """`trace.reduce` on the traces recorded before the program had spans,
    pinned; `spans.reduce` gives exactly the same where it has none."""
    golden = _load("reduce.golden.json")[name.removesuffix(".trace.json")]
    ex = _load(name)
    assert trace.reduce(ex, devices=1) == golden
    assert spans.reduce(ex, devices=1) == golden


@pytest.mark.parametrize("name", UNSPANNED)
def test_innermost_naming_agrees_where_spans_do_not_nest(name):
    ex = _load(name)
    (_, lo, dur), = [h for h in ex["host"] if h[0] == "window"]
    busy = trace.union([[o[1], o[1] + o[2]] for o in ex["devices"][0]["ops"]])
    mids = [(s + e) / 2 for s, e in trace.gaps(busy, lo, lo + dur)]
    mids += [h[1] - 1 for h in ex["host"]] + [h[1] + h[2] for h in ex["host"]]
    coverer = trace._coverer(ex["host"])
    assert spans.namer(ex["host"])(mids) == [coverer(t) for t in mids]


def test_idle_gaps_named_by_the_innermost_span():
    red = spans.reduce(HAND, devices=1)
    # gaps [0,22) mid 11: the benchmark's call only; [24,45) mid 34.5:
    # dispatch; [95,100) mid 97.5: readback; [104,140) mid 122: the second
    # call's root (stage_in starts at 125); [180,200) mid 190: no span (the
    # call ended at 190)
    assert dict(red["breakdown"]["idle_gaps"]) == pytest.approx({
        "solve_batch": 22e-9, "sptrsv.dispatch": 21e-9,
        "sptrsv.readback": 5e-9, "sptrsv.solve_batch": 36e-9,
        "no benchmark span": 20e-9})
    assert red["busy_s"] == pytest.approx(96e-9)


def test_self_times_and_host_metrics_by_hand():
    red = spans.reduce(HAND, devices=1)
    got = red["program_spans"]
    assert {k: v["count"] for k, v in got.items()} == {
        "sptrsv.solve_batch": 2, "sptrsv.executor_build": 1,
        "sptrsv.stage_in": 2, "sptrsv.dispatch": 2, "sptrsv.readback": 2}
    # roots: 96 - (6 + 10 + 10 + 65) and 68 - (5 + 5 + 53)
    assert {k: v["self_s"] for k, v in got.items()} == pytest.approx({
        "sptrsv.solve_batch": 10e-9, "sptrsv.executor_build": 6e-9,
        "sptrsv.stage_in": 15e-9, "sptrsv.dispatch": 15e-9,
        "sptrsv.readback": 118e-9})
    assert spans.per_call_ms(red, calls=2) == pytest.approx({
        "host_api_ms": 5e-6, "host_stage_ms": 7.5e-6,
        "host_dispatch_ms": 7.5e-6, "host_readback_ms": 59e-6})


def test_self_times_clip_to_the_window():
    mine = [h for h in HAND["host"] if spans.is_program(h[0])]
    got = spans.self_times(mine, 50, 150)
    # only the second root starts in [50, 150); the first keeps [50, 108)
    # less its readback's [50, 105), the second [121, 150) less 5 + 5 + 15
    assert got["sptrsv.solve_batch"]["count"] == 1
    assert got["sptrsv.solve_batch"]["self_s"] == pytest.approx(7e-9)
    assert got["sptrsv.executor_build"] == {"count": 0, "self_s": 0.0}
    assert got["sptrsv.readback"]["self_s"] == pytest.approx(70e-9)


@pytest.mark.parametrize("name", UNSPANNED)
def test_no_host_metric_without_program_spans(name):
    red = spans.reduce(_load(name), devices=1)
    assert "program_spans" not in red
    assert spans.per_call_ms(red, calls=30) == {}


def test_extract_reads_program_spans_from_a_profile(tmp_path):
    """A CPU profile of jax-backend calls: the program's spans come out
    beside the benchmark's, nested in its call spans."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.core import api
    from repro.core.matrices import generate

    prog = api.compile(generate("chem_bp"))
    b = np.ones((prog.n, 1), np.float32)
    api.solve_batch(prog, b)
    trace.start(tmp_path)
    try:
        with TraceAnnotation("window"):
            for _ in range(3):
                with TraceAnnotation("solve_batch"):
                    api.solve_batch(prog, b)
    finally:
        trace.stop()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = spans.extract(ProfileData.from_file(path))["host"]
    names = [h[0] for h in host]
    assert names.count("solve_batch") == 3
    for span in ["sptrsv.solve_batch"] + [
            f"sptrsv.{s}" for s in ("stage_in", "dispatch", "readback")]:
        assert names.count(span) == 3
    calls = [(s, s + d) for n, s, d in host if n == "solve_batch"]
    for n, s, d in host:
        if spans.is_program(n):
            assert any(lo <= s and s + d <= hi for lo, hi in calls)
    # self times add up to the roots' time
    (_, lo, dur), = [h for h in host if h[0] == "window"]
    mine = [h for h in host if spans.is_program(h[0])]
    got = spans.self_times(mine, lo, lo + dur)
    roots = sum(d for n, _, d in mine if n == "sptrsv.solve_batch")
    assert sum(v["self_s"] for v in got.values()) == pytest.approx(roots / 1e9)


def test_trim_keeps_the_first_calls():
    got = spans.trim(HAND, calls=1)
    assert got["host"][0] == ["window", 0, 110]
    assert [h[0] for h in got["host"]].count("solve_batch") == 1
    assert len(got["host"]) == 7
    assert [o[0] for o in got["devices"][0]["ops"]] == ["copy", "k", "copy.1"]


# --------------------------------------------------------------------------
# trimmed traces of 30 ckt_add20.b1 calls with the program's spans
@pytest.mark.parametrize("name", SPANNED)
def test_chip_trace_split(name):
    ex = _load(name)
    red = spans.reduce(ex, devices=1)
    host = ex["host"]
    calls = [h for h in host if h[0] == "solve_batch"]
    roots = [h for h in host if h[0] == "sptrsv.solve_batch"]
    assert len(calls) == len(roots) == 30
    got = red["program_spans"]
    for step in ("sptrsv.stage_in", "sptrsv.dispatch", "sptrsv.readback"):
        assert got[step]["count"] == 30
    assert "sptrsv.executor_build" not in got  # warm-up built it
    # the four host metrics by hand: each child's own duration, and the
    # root's less its children's
    by = {n: sum(d for m, _, d in host if m == n) for n in got}
    host_ms = spans.per_call_ms(red, calls=30)
    for metric, span in spans.HOST_METRICS.items():
        own = by[span] - (sum(by[c] for c in by if c != span)
                          if span == "sptrsv.solve_batch" else 0)
        assert host_ms[metric] == pytest.approx(own / 30 / 1e6, rel=1e-9)
    # together they are the program's whole call
    assert sum(host_ms.values()) == pytest.approx(
        by["sptrsv.solve_batch"] / 30 / 1e6, rel=1e-9)
    # idle time is the complement of busy time, however it is named
    idle = red["breakdown"]["idle_gaps"]
    assert sum(v for _, v in idle) == pytest.approx(
        red["window_s"] - red["busy_s"])
    named = sum(v for n, v in idle if spans.is_program(n))
    assert named >= 0.9 * sum(v for _, v in idle)
    # a gap is named after the innermost span around its midpoint
    (_, lo, dur), = [h for h in host if h[0] == "window"]
    busy = trace.union([[o[1], o[1] + o[2]] for o in ex["devices"][0]["ops"]])
    for s, e in trace.gaps(busy, lo, lo + dur):
        t = (s + e) / 2
        around = [h for h in host if h[0] != "window"
                  and h[1] <= t < h[1] + h[2]]
        want = max(around, key=lambda h: (h[1], -h[2]))[0] if around \
            else spans.NO_SPAN
        assert spans.namer(host)([t]) == [want]


def _offset_by_hand(ex):
    """Per call: the shifts of its kernel run that keep it after the
    call's dispatch starts and before its readback ends."""
    host = ex["host"]
    kernels = sorted(o for o in ex["devices"][0]["ops"]
                     if o[0] == "sptrsv_pallas.1")
    roots = sorted(h for h in host if h[0] == "sptrsv.solve_batch")
    assert len(kernels) == len(roots) == 30
    lo, hi = [], []
    for (_, s, d), k in zip(roots, kernels):
        inside = {h[0]: h for h in host
                  if s <= h[1] and h[1] + h[2] <= s + d}
        lo.append(inside["sptrsv.dispatch"][1] - k[1])
        hi.append(sum(inside["sptrsv.readback"][1:]) - k[1] - k[2])
    return max(lo), min(hi)


@pytest.mark.parametrize("name", SPANNED)
def test_clock_offset_by_hand(name):
    ex = _load(name)
    lo, hi = spans.clock_offset(ex)
    assert (lo, hi) == _offset_by_hand(ex)
    assert lo < hi  # one shift fits every call of a run


def test_device_clock_is_off_by_a_shift_that_differs_by_run():
    """In run a most kernel runs sit, on the trace's time line, before
    their call's dispatch started: its device ops need a shift of 0.26 ms
    or more to fall inside their calls.  The shifts that fit run b do not
    overlap run a's: the device-to-host mapping moves from run to run."""
    (a_lo, a_hi), (b_lo, b_hi) = [spans.clock_offset(_load(n))
                                  for n in SPANNED]
    assert a_lo > 0.25e6
    assert b_hi < a_lo


def test_clock_offset_needs_one_kernel_run_per_call():
    assert spans.clock_offset(_load(UNSPANNED[0])) is None
    ex = _load(SPANNED[0])
    ops = ex["devices"][0]["ops"]
    ex["devices"][0]["ops"] = [o for o in ops if o[3]][1:]
    assert spans.clock_offset(ex) is None
