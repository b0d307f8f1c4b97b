"""The SpTRSV kernel's share of its roofline, in %: the least time the
solve needs on this chip (`work.least_seconds` for one call's columns on
one device) over the kernel's measured time per call on the slowest
device."""

from benchmarks.chip import work


def read(ctx):
    tr = ctx["trace"]
    calls = ctx["window"]["attempted"]
    if tr is None or not calls or not any(tr["kernel_events"]):
        return None
    n, nnz = ctx["matrix"]
    least, _ = work.least_seconds(n, nnz, ctx["width"] // ctx["devices"],
                                  ctx["peak"])
    return 100.0 * least / (max(tr["kernel_s"]) / calls)
