"""Row order the compiler owns: the ``level_order`` pass (DESIGN.md §1, §6).

A lower-triangular solve may run its rows in any topological order, so the
compiler is free to relabel them.  The level order numbers rows by DAG
level (the longest path from a source), keeping the given order within a
level: the system ``P L Pᵀ`` it compiles is still lower triangular and its
solution is ``x`` permuted.  In level order the rows a cycle block touches
lie close together, so `kernels.sptrsv.ops.plan_window` finds a window that
slides where the natural order of a 3D stencil keeps nearly every row live
(at 32³: 6,192 against 27,408 of 32,768 rows).

The pass keeps the workload's order unless the resident kernel's x buffer
of one column cannot fit a core's VMEM (`kernels.common.resident_fits`);
every program that fits compiles exactly as it would without this pass.
"""

from __future__ import annotations

import numpy as np

from repro.kernels.common import resident_fits

from .ir import ComputeDag

__all__ = ["wants_level", "levels", "permute", "run", "user_provenance"]


def wants_level(n: int) -> bool:
    """Whether the pass relabels an ``n``-node workload by level: when one
    column of x cannot stay in a core's VMEM."""
    return not resident_fits(n)


def levels(dag: ComputeDag) -> np.ndarray:
    """Longest-path level of every node, computed one wavefront at a time:
    a node joins the wavefront after the one that holds its last source."""
    n = dag.n
    indeg = np.diff(dag.ptr)
    dst = np.repeat(np.arange(n, dtype=np.int64), indeg)
    by_src = np.argsort(dag.src, kind="stable")
    out_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(dag.src, minlength=n), out=out_ptr[1:])
    consumers = dst[by_src]
    level = np.zeros(n, np.int64)
    waiting = indeg.copy()
    front = np.flatnonzero(indeg == 0)
    depth = 0
    while front.size:
        level[front] = depth
        lo, cnt = out_ptr[front], out_ptr[front + 1] - out_ptr[front]
        idx = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt) + np.arange(cnt.sum())
        node, hits = np.unique(consumers[idx], return_counts=True)
        waiting[node] -= hits
        front = node[waiting[node] == 0]
        depth += 1
    return level


def permute(dag: ComputeDag, perm: np.ndarray) -> tuple[ComputeDag, np.ndarray]:
    """``dag`` relabelled so that its node ``k`` is ``dag``'s node
    ``perm[k]``; ``perm`` must be a topological order.  Returns the new
    workload, its sources ascending within each node as the frontend
    contract asks, and ``edge`` with ``edge[e]`` the index in ``dag`` of the
    new workload's edge ``e``."""
    n = dag.n
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    deg = np.diff(dag.ptr)[perm]
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=ptr[1:])
    edge = np.repeat(dag.ptr[perm] - ptr[:-1], deg) + np.arange(ptr[-1])
    src = inv[dag.src[edge]]
    by_row = np.lexsort((src, np.repeat(np.arange(n), deg)))
    edge, src = edge[by_row], src[by_row]
    return ComputeDag(name=dag.name, n=n, ptr=ptr, src=src,
                      weight=dag.weight[edge], scale=dag.scale[perm]), edge


def run(dag: ComputeDag):
    """The ``level_order`` pass: ``(dag, perm, edge, metrics)``.

    ``perm`` maps each node of the returned workload to the given one's
    (``None`` when the order is kept) and ``edge`` does the same for edges
    (see `permute`).  ``metrics``: whether it was ``reordered`` and, where
    the levels were computed (not for a kept order: a deep DAG costs one
    numpy step per level), the DAG's ``levels`` and ``widest_level``.
    """
    if not wants_level(dag.n):
        return dag, None, None, {"reordered": False}
    level = levels(dag)
    width = np.bincount(level, minlength=1)
    metrics = {"levels": int(width.size), "widest_level": int(width.max()),
               "reordered": False}
    perm = np.argsort(level, kind="stable")
    if np.array_equal(perm, np.arange(dag.n)):
        return dag, None, None, metrics
    new, edge = permute(dag, perm)
    metrics["reordered"] = True
    return new, perm.astype(np.int32), edge, metrics


def user_provenance(ss, perm, edge):
    """``stream_src`` of a relabelled program in the workload's own edge
    and node numbers, so `recompile_values` takes the workload as given."""
    if ss is None:
        return None
    out = np.empty_like(ss)
    is_edge = ss >= 0
    out[is_edge] = edge[ss[is_edge]]
    out[~is_edge] = -(perm[-(ss[~is_edge] + 1)].astype(np.int64) + 1)
    return out
