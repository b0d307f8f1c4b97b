"""Right-hand-side columns solved per second: every column completed in the
window over the window's length (closed-loop cells)."""


def read(ctx):
    w = ctx["window"]
    if "columns" not in w or w["window_s"] <= 0:
        return None
    return w["columns"] / w["window_s"]
