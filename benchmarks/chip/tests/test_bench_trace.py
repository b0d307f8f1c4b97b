"""`trace.py` on trimmed copies of traces recorded on a TPU v5e (30 calls
of the resident kernel on ckt_add20 at B=1, 3 calls of the blocked kernel
on band_huge64k at B=16), and on intervals made by hand."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import trace

FIXTURES = Path(__file__).parent / "fixtures"


def _load(name):
    with open(FIXTURES / name) as f:
        return json.load(f)


def test_union_merges_overlaps_and_touching():
    assert trace.union([[5, 7], [0, 2], [1, 3], [3, 4], [8, 9]]) == \
        [[0, 4], [5, 7], [8, 9]]


def test_gaps_are_the_complement_in_the_window():
    assert trace.gaps([[2, 4], [6, 7]], 0, 10) == [[0, 2], [4, 6], [7, 10]]
    assert trace.gaps([[0, 10]], 0, 10) == []


def test_op_name_of_hlo_line():
    assert trace.op_name('%sptrsv_pallas.1 = f32[2400,1]{1,0} custom-call('
                         '...), custom_call_target="tpu_custom_call"') == \
        "sptrsv_pallas.1"


def _by_hand(ex):
    """Busy and kernel time recomputed directly from the fixture."""
    (_, lo, dur), = [h for h in ex["host"] if h[0] == "window"]
    hi = lo + dur
    ops = ex["devices"][0]["ops"]
    covered = set()
    kernel = 0
    for _, s, d, k in ops:
        covered.update(range(max(s, lo), min(s + d, hi)))
        kernel += d if k else 0
    return len(covered), kernel, dur


@pytest.mark.parametrize("name,calls,kernel", [
    ("ckt_add20.b1.trace.json", 30, "sptrsv_pallas.1"),
    ("band_huge64k.b16.trace.json", 3, "sptrsv_pallas_blocked.1"),
])
def test_reduce_on_chip_trace(name, calls, kernel):
    ex = _load(name)
    red = trace.reduce(ex, devices=1)
    busy_ns, kernel_ns, window_ns = _by_hand(ex)
    assert red["window_s"] == pytest.approx(window_ns / 1e9)
    assert red["busy_s"] == pytest.approx(busy_ns / 1e9)
    assert red["kernel_s"][0] == pytest.approx(kernel_ns / 1e9)
    assert red["kernel_events"] == [calls]
    assert red["idle_share"][0] == pytest.approx(1 - busy_ns / window_ns)
    assert red["breakdown"]["device_ops"][0][0] == kernel
    # every idle moment of these windows falls inside a solve_batch call
    assert [g[0] for g in red["breakdown"]["idle_gaps"]] == ["solve_batch"]
    assert sum(g[1] for g in red["breakdown"]["idle_gaps"]) == \
        pytest.approx(red["window_s"] - red["busy_s"])


def test_kernel_time_per_call():
    red = trace.reduce(_load("ckt_add20.b1.trace.json"), devices=1)
    # the resident kernel ran 0.5148 ms per call on the chip
    assert red["kernel_s"][0] / 30 == pytest.approx(5.148e-4, rel=1e-3)


def test_reduce_needs_the_window_and_the_devices():
    ex = _load("band_huge64k.b16.trace.json")
    with pytest.raises(ValueError):
        trace.reduce(ex, devices=4)
    with pytest.raises(ValueError):
        trace.reduce({"devices": ex["devices"], "host": []}, devices=1)


def test_slowest_device_and_idle_naming():
    ex = {"host": [["window", 0, 100], ["submit", 10, 20], ["pump", 50, 10]],
          "devices": [{"name": "/device:TPU:0",
                       "ops": [["k", 0, 10, True], ["c", 30, 20, False]]},
                      {"name": "/device:TPU:1",
                       "ops": [["k", 0, 40, True], ["k", 35, 10, True]]}]}
    red = trace.reduce(ex, devices=2)
    assert red["kernel_s"] == [10e-9, 50e-9]
    assert red["busy_s"] == pytest.approx((30 + 45) / 2 * 1e-9)
    idle = dict(red["breakdown"]["idle_gaps"])
    # device 0 is idle in [10, 30) (midpoint inside submit) and [50, 100)
    # (midpoint 75, after pump's [50, 60) ended); device 1 in [45, 100)
    # (midpoint 72.5): a gap is named by the span around its midpoint
    assert idle["submit"] == pytest.approx(20e-9)
    assert idle["no benchmark span"] == pytest.approx(105e-9)
