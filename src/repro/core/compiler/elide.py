"""Stall-elide pass: dense `ScheduleIR` → emitted `EmitIR`.

Cycles in which no lane executes (bank-conflict replay, global psum
stalls) count as hardware time (``stats.cycles``) but carry no
information — an all-NOP row changes no state, so streaming it would be
pure instruction HBM traffic.  This pass drops them from the emitted
stream (``stats.emitted_cycles`` = rows kept).
"""

from __future__ import annotations

from .ir import EmitIR, ScheduleIR

__all__ = ["run"]


def run(sir: ScheduleIR) -> EmitIR:
    keep = (sir.ops != 0).any(axis=1)           # a lane executed this cycle
    stats = sir.stats
    stats.emitted_cycles = int(keep.sum())
    metrics = {
        "hardware_cycles": int(keep.size),
        "emitted_cycles": stats.emitted_cycles,
        "stall_rows_elided": int(keep.size) - stats.emitted_cycles,
    }
    return EmitIR(
        name=sir.name, n=sir.n,
        ops=sir.ops[keep], val_idx=sir.val_idx[keep], src=sir.src[keep],
        ctl=sir.ctl[keep], slot=sir.slot[keep],
        stream=sir.stream, num_slots=sir.num_slots,
        stats=stats, metrics=metrics,
        stream_src=sir.stream_src,
    )
