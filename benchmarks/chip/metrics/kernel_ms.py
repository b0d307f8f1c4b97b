"""Device ms of the SpTRSV kernel per solve call: the kernel's
``tpu_custom_call`` time in the traced window over the calls made, on the
slowest device."""


def read(ctx):
    tr = ctx["trace"]
    calls = ctx["window"]["attempted"]
    if tr is None or not calls or not any(tr["kernel_events"]):
        return None
    return max(tr["kernel_s"]) / calls * 1e3
