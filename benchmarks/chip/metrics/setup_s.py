"""Set-up: process start to the window's start (TPU init, matrix
generation, compile, warm-up), on the host clock."""


def read(ctx):
    return ctx["setup_s"]
