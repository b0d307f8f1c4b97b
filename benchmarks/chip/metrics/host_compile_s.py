"""Host seconds of the SpTRSV compiler (``api.compile``, or the service's
program-cache miss), on the benchmark's clock around the call."""


def read(ctx):
    return ctx["spans"].get("compile")
