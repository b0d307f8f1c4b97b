#!/usr/bin/env python3
"""The program's own host spans in a profile of the solve path.

The solve entry marks the host steps of each call with
`jax.profiler.TraceAnnotation` spans named ``sptrsv.*``
(`repro.core.executor` lists them): ``sptrsv.solve_batch`` around the
whole call, and nested in it ``sptrsv.executor_build`` (a cache miss),
``sptrsv.stage_in``, ``sptrsv.dispatch`` and ``sptrsv.readback``.  They
land in the same xplane as the device ops.

`extract` reads them beside what `trace.extract` reads.  `reduce` adds to
`trace.reduce`'s result:

* ``program_spans``: for each span name, the count of spans that start in
  the window and their self time inside it (each span's duration minus the
  union of the spans nested in it, both clipped to the window);
* ``breakdown.idle_gaps`` with each gap named after the innermost span, the
  benchmark's or the program's, that covers its midpoint.

A trace with no program span reduces exactly as `trace.reduce` reduces it.
`HOST_METRICS` names the per-call host times (self time over calls, ms).

Run as a script, it splits one cell's solve call on the chip:

    python3 benchmarks/chip/spans.py --workload <cell> --seed <n> \\
        --seconds <s> [--fixture <path> --fixture-calls 30]

One process sets the cell up as ``run.py`` does, drives an untraced
window, a traced one and another untraced one, and prints one JSON line:
calls per second of each window, the per-call host times, the program
spans, the idle gaps by name, the `clock_offset` interval, and the cost
of one span with the profiler off and on.  ``--fixture`` writes the traced
window's first calls in `trace.extract`'s format.

The device ops' times in the xplane are the device's clock mapped onto the
host's.  On a TPU v5e that mapping was found off by up to about 2 ms, by a
different amount in each run (`clock_offset`), so naming idle gaps after
host spans places time within a 2 ms call only as far as that allows;
self times, which read the host clock alone, are exact.
"""

from __future__ import annotations

import heapq
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parents[2])

from benchmarks.chip import trace  # noqa: E402

PREFIX = "sptrsv."
HOST_METRICS = {
    "host_api_ms": "sptrsv.solve_batch",
    "host_stage_ms": "sptrsv.stage_in",
    "host_dispatch_ms": "sptrsv.dispatch",
    "host_readback_ms": "sptrsv.readback",
}
NO_SPAN = "no benchmark span"


def is_program(name: str) -> bool:
    return name.startswith(PREFIX)


def extract(profile) -> dict:
    """`trace.extract`, with the program's host spans added to ``host``."""
    ex = trace.extract(profile)
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if is_program(ev.name):
                        ex["host"].append([ev.name, int(ev.start_ns),
                                           int(ev.duration_ns)])
    return ex


def namer(host: list):
    """``names_at(times)``: for each time, the innermost span (other than
    the window) that covers it: of the spans open at t, the one that
    started last, the shorter of two that started together."""
    spans = sorted((s, s + d, name) for name, s, d in host
                   if name != "window")

    def names_at(times) -> list:
        out = [NO_SPAN] * len(times)
        heap: list = []   # open spans, latest start on top
        i = 0
        for k in sorted(range(len(times)), key=times.__getitem__):
            t = times[k]
            while i < len(spans) and spans[i][0] <= t:
                s, e, name = spans[i]
                heapq.heappush(heap, (-s, e - s, e, name))
                i += 1
            while heap and heap[0][2] <= t:   # ended: t only grows
                heapq.heappop(heap)
            if heap:
                out[k] = heap[0][3]
        return out

    return names_at


def _clipped(s: int, e: int, lo: int, hi: int) -> int:
    return max(0, min(e, hi) - max(s, lo))


def self_times(spans: list, lo: int, hi: int) -> dict:
    """``{name: {"count", "self_s"}}`` over ``[name, start, duration]``
    spans that nest on one thread (see module docstring)."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    children: dict = {i: [] for i in order}
    stack: list = []   # indices of the spans open at the current start
    for i in order:
        _, s, d = spans[i]
        while stack and spans[stack[-1]][1] + spans[stack[-1]][2] <= s:
            stack.pop()
        if stack and s + d <= spans[stack[-1]][1] + spans[stack[-1]][2]:
            children[stack[-1]].append([max(s, lo), min(s + d, hi)])
        stack.append(i)
    out: dict = {}
    for i in order:
        name, s, d = spans[i]
        inner = sum(e - b for b, e in trace.union(
            [c for c in children[i] if c[1] > c[0]]))
        entry = out.setdefault(name, {"count": 0, "self_s": 0.0})
        entry["count"] += int(lo <= s < hi)
        entry["self_s"] += (_clipped(s, s + d, lo, hi) - inner) / 1e9
    return out


def reduce(ex: dict, devices: int, window_span: str = "window") -> dict:
    """`trace.reduce`, plus the program's spans (see module docstring)."""
    mine = [h for h in ex["host"] if is_program(h[0])]
    red = trace.reduce({"devices": ex["devices"],
                        "host": [h for h in ex["host"]
                                 if not is_program(h[0])]},
                       devices, window_span)
    if not mine:
        return red
    (_, lo, dur), = [h for h in ex["host"] if h[0] == window_span]
    hi = lo + dur
    names_at = namer(ex["host"])
    idle_by: dict = {}
    for d in ex["devices"][:devices]:
        busy = trace.union([[max(s, lo), min(s + n, hi)]
                            for _, s, n, _ in d["ops"]
                            if s + n > lo and s < hi])
        gaps = trace.gaps(busy, lo, hi)
        for (s, e), name in zip(gaps, names_at([(s + e) / 2
                                                for s, e in gaps])):
            idle_by[name] = idle_by.get(name, 0) + (e - s)
    top = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    red["breakdown"]["idle_gaps"] = [[n, v / 1e9] for n, v in top]
    red["program_spans"] = self_times(mine, lo, hi)
    return red


def per_call_ms(red: dict, calls: int) -> dict:
    """The `HOST_METRICS` a reduction gives: a span's self time in the
    window over the calls made, in ms; a span the trace lacks gives none."""
    spans = red.get("program_spans", {})
    if not calls:
        return {}
    return {metric: spans[name]["self_s"] / calls * 1e3
            for metric, name in HOST_METRICS.items() if name in spans}


def clock_offset(ex: dict, device: int = 0) -> tuple | None:
    """The shifts (ns, device time + shift = host time) that put every
    kernel run inside its call: after the call's ``sptrsv.dispatch``
    starts and before its ``sptrsv.readback`` ends.  Calls and kernel runs
    pair in order; None unless each call ran the kernel once.  An interval
    that excludes 0 shows the trace's device and host timelines apart."""
    host = ex["host"]
    roots = sorted(h[1:] for h in host if h[0] == "sptrsv.solve_batch")
    kernels = sorted(o[1:3] for o in ex["devices"][device]["ops"] if o[3])
    if not roots or len(roots) != len(kernels):
        return None
    marks = {n: sorted(h[1:] for h in host if h[0] == n)
             for n in ("sptrsv.dispatch", "sptrsv.readback")}
    if any(len(v) != len(roots) for v in marks.values()):
        return None
    lo = max(d[0] - k[0] for d, k in zip(marks["sptrsv.dispatch"], kernels))
    hi = min(r[0] + r[1] - k[0] - k[1]
             for r, k in zip(marks["sptrsv.readback"], kernels))
    return lo, hi


def trim(ex: dict, calls: int, call_span: str = "solve_batch",
         window_span: str = "window") -> dict:
    """The first ``calls`` calls of the window: the window span cut to end
    with the last of them, and the spans and device ops inside it."""
    (_, lo, _), = [h for h in ex["host"] if h[0] == window_span]
    ends = sorted(s + d for name, s, d in ex["host"]
                  if name == call_span and s >= lo)
    hi = ends[min(calls, len(ends)) - 1]
    host = [[window_span, lo, hi - lo]] + [
        h for h in ex["host"]
        if h[0] != window_span and h[1] >= lo and h[1] + h[2] <= hi]
    devs = [{"name": d["name"],
             "ops": [o for o in d["ops"] if o[1] >= lo and o[1] + o[2] <= hi]}
            for d in ex["devices"]]
    return {"devices": devs, "host": sorted(host, key=lambda h: h[1])}


# --------------------------------------------------------------------------
def span_cost_us(reps: int) -> float:
    """Host microseconds to enter and leave one `TraceAnnotation`."""
    import time

    from jax.profiler import TraceAnnotation

    t = time.perf_counter()
    for _ in range(reps):
        with TraceAnnotation("sptrsv.cost"):
            pass
    return (time.perf_counter() - t) / reps * 1e6


def main(argv: list[str] | None = None) -> int:
    import argparse
    import json
    import shutil

    import jax

    from benchmarks.chip import registry, run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", type=Path)
    ap.add_argument("--fixture-calls", type=int, default=30)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    try:
        run.require_tpu(cell.chips)
    except run.NoChip as e:
        return int(e.code)
    run.enable_cache()
    set_up: dict = {}
    mat, ref = run.build_matrix(cell.config)
    kind = run.DRIVERS[cell.traffic["driver"]](cell, mat, set_up)
    kind.prepare(args.seed, set_up)
    trace_dir = run.TRACE_DIR

    def window(traced: bool) -> dict:
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace.start(trace_dir)
        with jax.profiler.TraceAnnotation("window"):
            res = kind.window(args.seconds)
        if traced:
            trace.stop()
        res["calls_per_s"] = res["attempted"] / res["window_s"]
        return res

    untraced_a = window(False)
    traced = window(True)
    untraced_b = window(False)
    ex = extract(trace.load(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    red = reduce(ex, kind.devices)
    calls = traced["attempted"]
    roots = [d for name, s, d in ex["host"] if name == "sptrsv.solve_batch"]
    idle = red["breakdown"]["idle_gaps"]
    offset = clock_offset(ex)
    cost_off = span_cost_us(200_000)
    trace.start(trace_dir)
    try:
        cost_on = span_cost_us(50_000)
    finally:
        trace.stop()
        shutil.rmtree(trace_dir, ignore_errors=True)
    if args.fixture is not None:
        args.fixture.parent.mkdir(parents=True, exist_ok=True)
        args.fixture.write_text(json.dumps(
            trim(ex, args.fixture_calls), separators=(",", ":")))
    pairs = kind.answers(untraced_a) + kind.answers(traced) + \
        kind.answers(untraced_b)
    out = {
        "workload": args.workload, "seed": args.seed,
        "calls_per_s": {"untraced_a": untraced_a["calls_per_s"],
                        "traced": traced["calls_per_s"],
                        "untraced_b": untraced_b["calls_per_s"]},
        "traced_calls": calls,
        "host_ms": per_call_ms(red, calls),
        "solve_batch_mean_ms": sum(roots) / len(roots) / 1e6 if roots
        else None,
        "kernel_ms": max(red["kernel_s"]) / calls * 1e3 if calls else None,
        "idle_share": red["idle_share"],
        "idle_gaps": idle,
        "idle_named_by_program": sum(v for n, v in idle if is_program(n))
        / max(sum(v for _, v in idle), 1e-12),
        "program_spans": red.get("program_spans", {}),
        "clock_offset_us": None if offset is None
        else [v / 1e3 for v in offset],
        "span_cost_us": {"profiler_off": cost_off, "profiler_on": cost_on},
        "max_rel_err": run.compare(ref, pairs),
        "set_up_s": set_up,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
