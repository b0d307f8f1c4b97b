"""FROZEN pre-pipeline compiler (PR 5 equivalence reference — do not edit).

Verbatim copy of the monolithic `core/schedule.compile_program` as it stood
before the staged `core/compiler/` pipeline replaced it.  Used only by
`tests/test_compiler_pipeline.py` to assert the pipeline reproduces the
legacy instruction stream and stats bit-for-bit on the bundled matrix
suite.  Original docstring follows.

Cycle-accurate compiler for the medium-granularity dataflow (paper §IV).

This is the paper's custom compiler: it allocates coarse nodes to CUs in
topological order, then simulates the synchronized VLIW machine cycle by
cycle, applying

  * the medium-granularity dataflow (§IV-A): node = minimal *allocation*
    unit, edge = minimal *scheduling* unit;
  * the partial-sum caching mechanism (§IV-B) with the deadlock-avoiding
    capacity rules of Fig. 7;
  * the ICR reordering of intra-node edge computation (§IV-C, Algo. 2),
    implemented exactly (max-count category, tie -> min initial R-value)
    with a lazy max-heap;
  * an online banked-register-file model with value broadcast (same-source
    reads are free — the crossbar broadcasts one read to many CUs) and
    x_i-register-file spill modelling (§III-B live-range/spill discussion).

The output is a `Program`: a dense, branch-free VLIW instruction stream that
the numpy / JAX / Pallas executors run verbatim; the schedule length is the
hardware cycle count (the paper's compiler "can fully predict the behavior
of the hardware", §III-B — we lean on exactly that property for timing).

Deviations from the paper (DESIGN.md §5 "Deviations from the paper"):
  * bank assignment is online least-used-first-fit instead of offline greedy
    graph coloring — same mechanism, conservative (never fewer conflicts);
  * ICR examines a per-CU window of ready edges (default 16);
  * the Fig. 7 capacity rule does not provably exclude a global psum
    deadlock (all slots holding blocked parents while the only startable
    node needs a park); on a detected global stall we park one partial sum
    into emergency overflow slots (modelling a data-memory psum spill, as
    the paper's register-file spill path would) and count `dm_escapes`.
"""

from __future__ import annotations

import heapq
import time
from collections import Counter

import numpy as np

from repro.core.csr import TriCSR
from repro.core.program import (
    OP_EDGE,
    OP_FINAL,
    PS_KEEP,
    PS_LOAD,
    PS_RESET,
    PS_STORE_RESET,
    PS_SWAP,
    AccelConfig,
    Program,
    ScheduleStats,
    pack_instructions,
    packed_planes,
)

__all__ = ["compile_program", "allocate_nodes", "PSUM_OVERFLOW_SLOTS"]

PSUM_OVERFLOW_SLOTS = 4  # emergency data-memory-modelled psum spill slots


# ---------------------------------------------------------------------------
# Node -> CU allocation (topological order == row order for triangular L)
# ---------------------------------------------------------------------------
def allocate_nodes(mat: TriCSR, cfg: AccelConfig) -> list[list[int]]:
    p = cfg.num_cus
    tasks: list[list[int]] = [[] for _ in range(p)]
    if cfg.alloc == "roundrobin":
        for i in range(mat.n):
            tasks[i % p].append(i)
        return tasks
    if cfg.alloc != "least_edges":
        raise ValueError(f"unknown alloc policy {cfg.alloc!r}")
    indeg = mat.in_degree()
    heap = [(0, c) for c in range(p)]  # (load, cu) — least accumulated work
    heapq.heapify(heap)
    for i in range(mat.n):
        w, c = heapq.heappop(heap)
        tasks[c].append(i)
        heapq.heappush(heap, (w + int(indeg[i]) + 1, c))
    return tasks


class _Node:
    __slots__ = (
        "nid", "owner", "srcs", "val_of", "ready", "pending",
        "remaining", "started", "solved", "slot",
    )

    def __init__(self, nid: int, owner: int, srcs, val_idx):
        self.nid = nid
        self.owner = owner
        self.srcs = srcs
        self.val_of = dict(zip(srcs.tolist(), val_idx.tolist()))
        self.ready: list[int] = []
        self.pending = len(srcs)
        self.remaining = len(srcs)
        self.started = False
        self.solved = False
        self.slot = -1

    def has_work(self) -> bool:
        return bool(self.ready) or (self.remaining == 0 and not self.solved)


class _CU:
    __slots__ = (
        "cid", "tasks", "pos_of", "head", "started_mask", "current",
        "cached", "free_slots", "free_over", "next_over", "resident",
        "spilled", "done_count", "edge_count",
    )

    def __init__(self, cid: int, tasks: list[int], psum_words: int):
        self.cid = cid
        self.tasks = tasks
        self.pos_of = {nd: k for k, nd in enumerate(tasks)}
        self.head = 0
        self.started_mask = np.zeros(len(tasks), dtype=bool)
        self.current: _Node | None = None
        self.cached: list[_Node] = []
        self.free_slots = list(range(psum_words))
        self.free_over = list(range(psum_words, psum_words + PSUM_OVERFLOW_SLOTS))
        self.next_over = psum_words + PSUM_OVERFLOW_SLOTS  # grows on demand
        self.resident: dict[int, int] = {}
        self.spilled: set[int] = set()
        self.done_count = 0
        self.edge_count = 0

    def peek_over_slot(self) -> int:
        """Next overflow slot (modelled data-memory psum spill; unbounded)."""
        if self.free_over:
            return self.free_over[0]
        if self.next_over > 250:
            raise RuntimeError("psum overflow slots exhausted (>250)")
        return self.next_over

    def advance_head(self) -> None:
        while self.head < len(self.tasks) and self.started_mask[self.head]:
            self.head += 1

    def release_slot(self, slot: int, psum_words: int) -> None:
        if slot < psum_words:
            self.free_slots.append(slot)
        else:
            self.free_over.append(slot)

    def all_done(self) -> bool:
        return self.done_count == len(self.tasks)


def _icr_assign(edge_cus, cands):
    """Algorithm 2 of the paper, exact, via a lazy max-heap.

    Returns {cu: src}.  Categories = distinct source nodes; repeatedly pick
    the category with the most remaining edges (tie -> smallest initial
    R-value, then smallest id), assign it to every CU that has it, remove
    those CUs, and recount.
    """
    cnt: Counter = Counter()
    cu_of_src: dict[int, list[int]] = {}
    for c in edge_cus:
        for s in cands[c]:
            cnt[s] += 1
            cu_of_src.setdefault(s, []).append(c)
    r_value = dict(cnt)
    heap = [(-v, r_value[s], s) for s, v in cnt.items()]
    heapq.heapify(heap)
    assigned: dict[int, int] = {}
    unassigned = set(edge_cus)
    while unassigned and heap:
        negv, _, s = heapq.heappop(heap)
        if cnt.get(s, 0) != -negv:
            continue  # stale entry
        for c in cu_of_src[s]:
            if c in unassigned:
                assigned[c] = s
                unassigned.discard(c)
                for s2 in cands[c]:
                    v = cnt.get(s2, 0)
                    if v > 0:
                        cnt[s2] = v - 1
                        if v > 1:
                            heapq.heappush(heap, (-(v - 1), r_value[s2], s2))
                        else:
                            del cnt[s2]
    return assigned


def compile_program(mat: TriCSR, cfg: AccelConfig | None = None, *,
                    planes: int | None = None) -> Program:
    """Compile ``mat`` into a packed VLIW `Program`.

    ``planes`` forces the packed-word layout (1 = single-word, 2 = the
    large-n fallback); ``None`` auto-selects via `program.packed_planes`.
    Cycles in which no lane executes (bank-conflict replay / global stalls)
    are counted in ``stats.cycles`` (the hardware cycle count) but *elided*
    from the emitted instruction stream — an all-NOP row carries no
    information, so streaming it would be pure HBM traffic
    (``stats.emitted_cycles`` counts the rows actually emitted).
    """
    cfg = cfg or AccelConfig()
    if cfg.dataflow not in ("medium", "coarse"):
        raise ValueError(f"unknown dataflow {cfg.dataflow!r}")
    t0 = time.perf_counter()
    n, p = mat.n, cfg.num_cus
    inv_diag = 1.0 / mat.diag()

    task_lists = allocate_nodes(mat, cfg)
    owner = np.empty(n, dtype=np.int64)
    for c, ts in enumerate(task_lists):
        for nid in ts:
            owner[nid] = c

    nodes: list[_Node] = []
    consumers: list[list[int]] = [[] for _ in range(n)]
    for i in range(n):
        lo, hi = int(mat.rowptr[i]), int(mat.rowptr[i + 1])
        srcs = mat.colidx[lo : hi - 1]
        nodes.append(_Node(i, int(owner[i]), srcs, np.arange(lo, hi - 1)))
        for j in srcs:
            consumers[j].append(i)

    cus = [_CU(c, task_lists[c], cfg.psum_words) for c in range(p)]
    startable: list[dict[int, int]] = [dict() for _ in range(p)]  # pos -> nid
    for nd in nodes:
        if nd.pending == 0:
            c = nd.owner
            startable[c][cus[c].pos_of[nd.nid]] = nd.nid

    ops_t, val_t, src_t, pct_t, psl_t = [], [], [], [], []
    stream: list[float] = []
    stats = ScheduleStats(name=mat.name, n=n, nnz=mat.nnz, cycles=0,
                          exec_edges=0, exec_finals=0)

    bank_of: dict[int, int] = {}
    bank_load = np.zeros(cfg.num_banks, dtype=np.int64)
    bank_free_order = list(range(cfg.num_banks))

    solved_total = 0
    cycle = 0
    stall_streak = 0
    values = mat.values
    max_cycles = 8 * mat.nnz + 64 * n + 4096

    while solved_total < n:
        if cycle > max_cycles:
            raise RuntimeError(f"scheduler did not converge on {mat.name}")
        op_row = np.zeros(p, dtype=np.uint8)
        val_row = np.zeros(p, dtype=np.int32)
        src_row = np.zeros(p, dtype=np.int32)
        pct_row = np.zeros(p, dtype=np.uint8)
        psl_row = np.zeros(p, dtype=np.uint8)

        # ---------------------------------------------- phase 1: node choice
        chosen: list[tuple[str, _Node, int, int] | None] = [None] * p
        nop_kind: list[str | None] = [None] * p

        for cu in cus:
            c = cu.cid
            if cu.all_done():
                nop_kind[c] = "l"
                continue
            cur = cu.current
            cur_live = cur is not None and not cur.solved

            if cfg.dataflow == "coarse":
                cu.advance_head()
                if cur_live and cur.has_work():
                    kind = "edge" if cur.ready else "final"
                    chosen[c] = (kind, cur, PS_KEEP, 0)
                elif not cur_live and cu.head < len(cu.tasks):
                    nd = nodes[cu.tasks[cu.head]]
                    if nd.pending == 0:
                        kind = "edge" if nd.ready else "final"
                        chosen[c] = (kind, nd, PS_RESET, 0)
                    else:
                        nop_kind[c] = "d"
                else:
                    nop_kind[c] = "d"
                continue

            picked: tuple[str, _Node] | None = None
            for nd in cu.cached:  # cached nodes have absolute priority
                if nd.has_work():
                    picked = ("resume", nd)
                    break
            if picked is None and cur_live and cur.has_work():
                picked = ("continue", cur)
            if picked is None and startable[c] and (cfg.psum_cache or not cur_live):
                pos = min(startable[c])
                picked = ("start", nodes[startable[c][pos]])
            if picked is None:
                # deadlock escape (also required with psum_cache=False: a
                # blocked current node can circularly wait on unstarted
                # nodes — see docstring)
                if stall_streak >= 2 and cur_live and startable[c]:
                    pos = min(startable[c])
                    nd = nodes[startable[c][pos]]
                    stats.dm_escapes += 1
                    kind = "edge" if nd.ready else "final"
                    chosen[c] = (kind, nd, PS_STORE_RESET, cu.peek_over_slot())
                    continue
                nop_kind[c] = "d"
                continue

            mode, nd = picked
            if mode == "resume":
                if cur_live:
                    ctrl, slot = PS_SWAP, nd.slot  # read-before-write swap
                else:
                    ctrl, slot = PS_LOAD, nd.slot
            elif mode == "continue":
                ctrl, slot = PS_KEEP, 0
            else:  # start
                if cur_live:
                    cu.advance_head()
                    first_new = (cu.head < len(cu.tasks)
                                 and cu.tasks[cu.head] == nd.nid)
                    need = 1 if first_new else 2
                    if len(cu.free_slots) < need:
                        if stall_streak >= 2:
                            # emergency psum overflow park (DESIGN.md §5)
                            ctrl, slot = PS_STORE_RESET, cu.peek_over_slot()
                            stats.dm_escapes += 1
                            kind = "edge" if nd.ready else "final"
                            chosen[c] = (kind, nd, ctrl, slot)
                            continue
                        nop_kind[c] = "p"
                        continue
                    ctrl, slot = PS_STORE_RESET, cu.free_slots[0]
                else:
                    ctrl, slot = PS_RESET, 0
            kind = "edge" if nd.ready else "final"
            chosen[c] = (kind, nd, ctrl, slot)

        # ---------------------------------------------- phase 2: ICR + banks
        edge_cus = [c for c in range(p) if chosen[c] and chosen[c][0] == "edge"]
        assigned_src: dict[int, int] = {}
        if edge_cus:
            w = cfg.icr_window
            cands = {c: chosen[c][1].ready[:w] for c in edge_cus}
            if cfg.icr:
                assigned_src = _icr_assign(edge_cus, cands)
            else:
                for c in edge_cus:  # traditional ascending-source-id pick
                    assigned_src[c] = min(chosen[c][1].ready)

            group = Counter(assigned_src.values())
            stats.distinct_reads += len(group)
            stats.reuse_events += sum(v - 1 for v in group.values())
            k = len(group)
            stats.constraints += k * (k - 1) // 2

            # banked-read model: one distinct address per bank per cycle;
            # identical addresses broadcast for free via the crossbar.
            used_banks: dict[int, int] = {}
            for s in sorted(group, key=lambda s_: (-group[s_], s_)):
                if s not in bank_of:
                    free = [b for b in bank_free_order if b not in used_banks]
                    pool = free if free else bank_free_order
                    b = min(pool, key=lambda b_: (bank_load[b_], b_))
                    bank_of[s] = b
                    bank_load[b] += 1
                b = bank_of[s]
                if b in used_banks and used_banks[b] != s:
                    for c in [c_ for c_, ss in assigned_src.items() if ss == s]:
                        del assigned_src[c]
                        chosen[c] = None
                        nop_kind[c] = "b"
                        stats.conflicts += 1
                else:
                    used_banks[b] = s

            # x_i register-file spill-reload model
            for c in list(assigned_src):
                s = assigned_src[c]
                cu = cus[c]
                if s in cu.spilled:
                    cu.spilled.discard(s)
                    if len(cu.resident) >= cfg.xi_words:
                        evict = min(cu.resident, key=cu.resident.get)
                        cu.spilled.add(evict)
                        del cu.resident[evict]
                    cu.resident[s] = 1
                    del assigned_src[c]
                    chosen[c] = None
                    nop_kind[c] = "s"

        # ---------------------------------------------- phase 3: execute
        newly_solved: list[_Node] = []
        executed = 0
        for c in range(p):
            if chosen[c] is None:
                k = nop_kind[c]
                if k == "b":
                    stats.bnop += 1
                elif k == "p":
                    stats.pnop += 1
                elif k == "s":
                    stats.snop += 1
                elif k == "l":
                    stats.lnop += 1
                else:
                    stats.dnop += 1
                continue
            executed += 1
            kind, nd, ctrl, slot = chosen[c]
            cu = cus[c]
            cur = cu.current

            if ctrl == PS_SWAP:
                cur.slot = nd.slot
                cu.cached[cu.cached.index(nd)] = cur
                nd.slot = -1
            elif ctrl == PS_LOAD:
                cu.release_slot(nd.slot, cfg.psum_words)
                cu.cached.remove(nd)
                nd.slot = -1
            elif ctrl == PS_STORE_RESET:
                if slot < cfg.psum_words:
                    cu.free_slots.remove(slot)
                elif slot in cu.free_over:
                    cu.free_over.remove(slot)
                else:
                    assert slot == cu.next_over
                    cu.next_over += 1
                cur.slot = slot
                cu.cached.append(cur)

            if not nd.started:
                nd.started = True
                pos = cu.pos_of[nd.nid]
                cu.started_mask[pos] = True
                startable[c].pop(pos, None)
                cu.advance_head()
            cu.current = nd

            pct_row[c] = ctrl
            psl_row[c] = slot

            if kind == "edge":
                s = assigned_src[c]
                nd.ready.remove(s)
                nd.remaining -= 1
                cu.edge_count += 1
                if s in cu.resident:
                    cu.resident[s] -= 1
                    if cu.resident[s] <= 0:
                        del cu.resident[s]  # release after last use (R_vs)
                op_row[c] = OP_EDGE
                val_row[c] = len(stream)
                stream.append(float(values[nd.val_of[s]]))
                src_row[c] = s
                stats.exec_edges += 1
            else:
                op_row[c] = OP_FINAL
                val_row[c] = len(stream)
                stream.append(float(inv_diag[nd.nid]))
                src_row[c] = nd.nid  # FINAL writes x[src]: out_idx is derived
                nd.solved = True
                cu.done_count += 1
                newly_solved.append(nd)
                stats.exec_finals += 1

        stall_streak = 0 if executed else stall_streak + 1

        # deliver newly solved values — consumable from the NEXT cycle
        for nd in newly_solved:
            solved_total += 1
            j = nd.nid
            per_cu_uses: dict[int, int] = {}
            for i in consumers[j]:
                cons = nodes[i]
                cons.ready.append(j)
                cons.pending -= 1
                cu_i = cons.owner
                per_cu_uses[cu_i] = per_cu_uses.get(cu_i, 0) + 1
                if not cons.started:
                    startable[cu_i][cus[cu_i].pos_of[i]] = i
            for cu_i, uses in per_cu_uses.items():
                cu = cus[cu_i]
                if len(cu.resident) < cfg.xi_words:
                    cu.resident[j] = cu.resident.get(j, 0) + uses
                else:
                    cu.spilled.add(j)
                    stats.spilled_values += 1

        if executed:
            ops_t.append(op_row)
            val_t.append(val_row)
            src_t.append(src_row)
            pct_t.append(pct_row)
            psl_t.append(psl_row)
        # else: all-NOP stall cycle — counts as hardware time but is elided
        # from the emitted stream (no state changes, no traffic needed)
        cycle += 1

    stats.cycles = cycle
    stats.emitted_cycles = len(ops_t)
    stats.per_cu_edges = np.array([cu.edge_count for cu in cus])
    num_slots = max(cu.next_over for cu in cus)

    instr = pack_instructions(
        np.stack(ops_t), np.stack(src_t), np.stack(pct_t), np.stack(psl_t),
        planes=planes if planes is not None else packed_planes(n),
    )
    stats.compile_seconds = time.perf_counter() - t0

    return Program(
        num_slots=num_slots,
        config=cfg,
        n=n,
        instr=instr,
        val_idx=np.stack(val_t),
        stream=np.array(stream, dtype=np.float32),
        stats=stats,
    )
