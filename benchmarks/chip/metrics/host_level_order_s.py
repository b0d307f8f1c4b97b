"""Host seconds of the compiler's row-order pass (``prog.stats.pass_stats``
entry ``level_order``): the DAG levels and the relabelled workload, in
``api.compile``.  None where the program records no such pass."""


def read(ctx):
    for p in ctx["program_stats"].pass_stats or ():
        if p.name == "level_order":
            return p.seconds
    return None
