"""Instruction rows the compiler emitted (``prog.stats.emitted_cycles``):
the serial steps of one pass of the kernel.  A count; it repeats exactly."""


def read(ctx):
    return ctx["program_stats"].emitted_cycles
