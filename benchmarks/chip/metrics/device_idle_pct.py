"""Share of the traced window in which no operation ran on the device, in
%, averaged over the cell's chips: 1 - (union of op intervals / window)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
