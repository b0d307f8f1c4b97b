"""Host seconds of the compiler's ICR reorder (``prog.stats.pass_stats``
entry ``icr_reorder``): the per-cycle source reordering with its bank and
spill models, summed over the cycles of ``api.compile``.  None where the
program records no such pass."""


def read(ctx):
    for p in ctx["program_stats"].pass_stats or ():
        if p.name == "icr_reorder":
            return p.seconds
    return None
