"""Host seconds of the compiler's psum-cache schedule pass
(``prog.stats.pass_stats`` entry ``psum_schedule``), the ICR reorder that
runs inside it excluded.  None where the program records no such pass."""


def read(ctx):
    for p in ctx["program_stats"].pass_stats or ():
        if p.name == "psum_schedule":
            return p.seconds
    return None
