"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps.

`start`/`stop` record one trace of the measured window (the host's Python
tracer off: the benchmark's own spans are `jax.profiler.TraceAnnotation`s).
`extract` reads the ``.xplane.pb`` into plain lists:

* for each TPU device plane, the events of its ``XLA Ops`` line as
  ``[name, start_ns, duration_ns, is_kernel]``; the SpTRSV kernel is the
  device's ``tpu_custom_call`` (a Pallas/Mosaic kernel) events;
* the host spans the benchmark annotates (`HOST_SPANS`) as
  ``[name, start_ns, duration_ns]``.

`reduce` works on those lists only, so the tests check it on a trimmed
copy of a trace recorded on the chip.  Busy time is the union of a
device's op intervals inside the window span; idle gaps are the rest of
the window, each named after the host span that covers its midpoint.
"""

from __future__ import annotations

import bisect
import glob
import os

HOST_SPANS = ("window", "api.compile", "solve_batch", "submit", "pump")
KERNEL_MARK = "tpu_custom_call"
OPS_LINE = "XLA Ops"


def start(log_dir) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load(log_dir):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    return ProfileData.from_file(paths[0])


def op_name(name: str) -> str:
    """``%sptrsv_pallas.1 = f32[...] custom-call(...)`` -> ``sptrsv_pallas.1``:
    the TPU trace names an op by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")


def _is_kernel(ev) -> bool:
    if KERNEL_MARK in ev.name:
        return True
    return any(isinstance(v, str) and KERNEL_MARK in v for _, v in ev.stats)


def extract(profile) -> dict:
    """Plain lists of device ops and host spans (see module docstring)."""
    devices, host = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append([op_name(ev.name), int(ev.start_ns),
                                int(ev.duration_ns), _is_kernel(ev)])
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"devices": devices, "host": host}


def union(intervals) -> list:
    """Merged ``[start, end)`` intervals, sorted."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list, lo: int, hi: int) -> list:
    """The parts of ``[lo, hi)`` that no busy interval covers."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append([cur, s])
        cur = max(cur, e)
    if cur < hi:
        out.append([cur, hi])
    return out


def _coverer(host: list):
    """``name_at(t)``: the benchmark span (other than the window) around
    time t.  Those spans do not nest, so a bisection finds it."""
    spans = sorted((s, s + d, name) for name, s, d in host
                   if name != "window")
    starts = [s for s, _, _ in spans]

    def name_at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < spans[i][1]:
            return spans[i][2]
        return "no benchmark span"

    return name_at


def reduce(ex: dict, devices: int, window_span: str = "window") -> dict:
    """Per-device busy, kernel and idle numbers inside the window span.

    Returns ``busy_s`` and ``window_s`` (busy averaged over the first
    ``devices`` devices), ``kernel_s`` and ``kernel_events`` per device,
    ``idle_share`` per device, and the ``breakdown`` of the result line:
    the ten ops with most device time (summed over devices) and the idle
    time named after the host span it fell in (summed over devices)."""
    win = [h for h in ex["host"] if h[0] == window_span]
    if len(win) != 1:
        raise ValueError(f"expected one {window_span!r} span, "
                         f"found {len(win)}")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    devs = ex["devices"][:devices]
    if len(devs) < devices:
        raise ValueError(f"trace has {len(ex['devices'])} TPU planes, "
                         f"the cell uses {devices}")
    busy_ns, kernel_ns, kernel_n, idle = [], [], [], []
    by_op: dict = {}
    idle_by: dict = {}
    name_at = _coverer(ex["host"])
    for d in devs:
        ops = [o for o in d["ops"] if o[1] + o[2] > lo and o[1] < hi]
        busy = union(_clip([[o[1], o[1] + o[2]] for o in ops], lo, hi))
        b = sum(e - s for s, e in busy)
        busy_ns.append(b)
        idle.append(1.0 - b / (hi - lo))
        ks = [o for o in ops if o[3]]
        kernel_ns.append(sum(min(o[1] + o[2], hi) - max(o[1], lo)
                             for o in ks))
        kernel_n.append(len(ks))
        for name, s, dur, _ in ops:
            by_op[name] = by_op.get(name, 0) + dur
        for s, e in gaps(busy, lo, hi):
            key = name_at((s + e) / 2)
            idle_by[key] = idle_by.get(key, 0) + (e - s)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "kernel_s": [k / 1e9 for k in kernel_ns],
        "kernel_events": kernel_n,
        "idle_share": idle,
        "breakdown": {"device_ops": [[n, v / 1e9] for n, v in top],
                      "idle_gaps": [[n, v / 1e9] for n, v in top_idle]},
    }


def summarize(profile, devices: int, window_span: str = "window") -> dict:
    return reduce(extract(profile), devices, window_span)
