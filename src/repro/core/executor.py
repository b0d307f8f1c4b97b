"""Executors for compiled VLIW programs.

Three implementations of identical semantics:
  * `execute_numpy`  — per-cycle numpy loop, vectorized over CUs and batch
                       (debug oracle);
  * `execute_jax`    — `jax.lax.scan` over cycles, fully vectorized over CUs
                       and right-hand sides (the production CPU/TPU path);
  * the Pallas kernel in `repro.kernels.sptrsv` (`make_pallas_executor`):
    VMEM-resident register files, double-buffered async-DMA instruction
    streaming, and — for n too large for VMEM residency — the HBM-resident
    row-blocked x/b placement with level-boundary window streaming
    (DESIGN.md §1).

Per-cycle semantics (see program.py): the psum control is applied first
(it configures the S1/S2 muxes and psum register file of Fig. 4b), then the
PE op executes.  Edges only ever read x values finalized in *earlier*
cycles (the scheduler guarantees it), so a cycle can be evaluated as one
parallel gather/FMA/scatter over all CUs.

Batched multi-RHS execution
---------------------------
The instruction stream depends only on the matrix L, not on b, so one pass
over the stream can solve `B` right-hand sides at once: state becomes
``x[n_pad, B]``, ``feedback[P, B]``, ``rf[P, S, B]`` and every per-cycle
gather/FMA/select/scatter broadcasts the instruction word over the batch
axis.  This amortizes instruction-stream traffic and jit/dispatch overhead
across the batch — the software analogue of streaming the VLIW program once
while the datapath processes many vectors.

Executors are cached per compiled program and *padded* batch width
(`pad_batch`), so repeated solves — including nearby batch sizes that pad
to the same width — never retrace.

Multi-device: `repro.core.shard` maps `build_solve_cols` over per-device
column blocks of the batch axis with `shard_map` (its own cache, keyed per
(program, padded per-device width, mesh)); `trace_count` observes both
paths.

Profiler spans
--------------
The per-call host path carries `jax.profiler.TraceAnnotation` spans, all
named ``sptrsv.*``, so a profile splits a solve call into its host steps
(the host clock; the profile's device ops are mapped onto it only to
within a couple of milliseconds).  They wrap host Python only, never code
inside a jitted function, and cost under a microsecond each when no
profiler runs (0.4 µs on a TPU v5e host):

  * ``sptrsv.solve_batch``    — one `api.solve_batch` call (the root);
  * ``sptrsv.executor_build`` — an executor-cache miss: staging the
                                instructions, placement, building the closure;
  * ``sptrsv.stage_in``       — copying b to the device, padding, placing;
  * ``sptrsv.dispatch``       — enqueueing the jitted solve and the slice of
                                the padded columns (not the device work);
  * ``sptrsv.readback``       — waiting for the device and copying x back.
"""

from __future__ import annotations

import weakref

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from .program import (
    OP_EDGE,
    OP_FINAL,
    PS_LOAD,
    PS_RESET,
    PS_STORE_RESET,
    PS_SWAP,
    Program,
    decode_instructions,
)
from .compiler import PSUM_OVERFLOW_SLOTS

__all__ = [
    "as_batch",
    "batched_entry",
    "build_solve_cols",
    "cached_entries",
    "execute_numpy",
    "execute_jax",
    "make_jax_executor",
    "make_pallas_executor",
    "pad_batch",
    "permute_rows",
    "trace_count",
    "validate_backend",
]

BATCH_PAD = 8  # batch widths are padded to a multiple of this (lane-friendly)

# Bumped (at trace time only) whenever a jax executor is traced; tests use it
# to assert the per-program cache prevents retracing.
_TRACE_COUNT = 0

# prog -> {padded_batch_width -> jitted solve}; weak keys let programs die.
_EXEC_CACHE: "weakref.WeakKeyDictionary[Program, dict]" = weakref.WeakKeyDictionary()


def trace_count() -> int:
    """Number of jax-executor traces so far (cache-hit observability)."""
    return _TRACE_COUNT


def cached_entries(prog: Program) -> list:
    """Keys of the per-program executor cache (cache-hit observability).

    Jax entries are padded-width ints (the cache-key contract asserted in
    `_cached_executor`); pallas entries are ``("pallas", width, *knobs)``
    tuples.  The serving tests use this to prove micro-batch bucketing
    never creates a key the contract forbids."""
    return sorted(_EXEC_CACHE.get(prog, {}), key=repr)


def pad_batch(width: int) -> int:
    """Round a batch width up to the lane-friendly padded width."""
    if width <= 1:
        return 1
    return -(-width // BATCH_PAD) * BATCH_PAD


def as_batch(b: np.ndarray, dtype=None) -> tuple[np.ndarray, bool]:
    """Normalize a RHS to ``([n, B], was_1d)`` — shared by all executors.

    With ``dtype=None``, arrays (including device-resident jax arrays) pass
    through without a host copy; only array-likes are coerced.
    """
    if dtype is not None or not hasattr(b, "ndim"):
        b = np.asarray(b, dtype=dtype)
    single = b.ndim == 1
    return (b[:, None] if single else b), single


def _psum_slots(prog: Program) -> int:
    base = prog.config.psum_words + PSUM_OVERFLOW_SLOTS
    return max(base, prog.num_slots or 0)


def execute_numpy(prog: Program, b: np.ndarray) -> np.ndarray:
    """Reference interpretation of the instruction stream.

    Accepts ``b`` of shape ``[n]`` (single RHS) or ``[n, B]`` (batched);
    returns ``x`` of the matching shape.  Each cycle is evaluated as one
    vectorized gather/FMA/select/scatter over all CUs and all RHS columns.
    """
    bmat, single = as_batch(b, dtype=np.float64)
    nb = bmat.shape[1]
    perm = prog.row_perm
    if perm is not None:
        bmat = bmat[perm]

    n, p = prog.n, prog.num_cus
    x = np.zeros((n + 1, nb), dtype=np.float64)
    feedback = np.zeros((p, nb), dtype=np.float64)
    rf = np.zeros((p, _psum_slots(prog), nb), dtype=np.float64)
    stream = prog.stream.astype(np.float64)
    lanes = np.arange(p)
    planes = prog.planes

    for t in range(prog.cycles):
        # shared packed decode — NOP lanes carry word 0, i.e. ctrl PS_KEEP
        op, src, ctrl, slot = decode_instructions(prog.instr[t], planes)
        slot = slot.astype(np.intp)
        ctb = ctrl[:, None]

        pv = feedback
        slot_val = rf[lanes, slot]  # [p, nb]
        # psum control mux (S1/S2 of Fig. 4b)
        pv = np.where(ctb == PS_RESET, 0.0, pv)
        pv = np.where(ctb == PS_LOAD, slot_val, pv)
        store = (ctrl == PS_STORE_RESET) | (ctrl == PS_SWAP)
        rf[lanes[store], slot[store]] = feedback[store]
        pv = np.where(ctb == PS_STORE_RESET, 0.0, pv)
        pv = np.where(ctb == PS_SWAP, slot_val, pv)

        v = stream[prog.val_idx[t]][:, None]  # [p, 1]
        edge = op == OP_EDGE
        pv = np.where(edge[:, None], pv + v * x[src], pv)
        fin = op == OP_FINAL
        if fin.any():
            # FINAL writes x[src] (the derived out index); finalized rows
            # are distinct within a cycle (scheduler guarantee)
            x[src[fin]] = (bmat[src[fin]] - pv[fin]) * v[fin]
        feedback = pv
    xr = x[:n]
    if perm is not None:
        xr = np.empty_like(xr)
        xr[perm] = x[:n]
    return xr[:, 0] if single else xr


def build_solve_cols(prog: Program, width: int):
    """Unjitted `solve(b[n, width]) -> x[n, width]` over the instruction stream.

    All instruction arrays become constants folded into the jaxpr; the
    cycle loop is a `lax.scan` whose carry is (x, feedback, psum_rf), each
    carrying a trailing batch axis of `width` RHS columns.

    This is the trace target shared by the local jit path below and the
    multi-device `shard_map` path (`repro.core.shard`), which maps it over
    per-device column blocks with the instruction constants replicated.
    A program with a ``row_perm`` gathers b into its row order and x back
    out of it inside the trace (`permute_rows`).
    """
    n, p = prog.n, prog.num_cus
    planes = prog.planes
    instr_words = jnp.asarray(prog.instr)  # [T, planes, P] packed
    vidx = jnp.asarray(prog.val_idx)
    stream = jnp.asarray(prog.stream, dtype=jnp.float32)
    nslots = _psum_slots(prog)
    lanes = jnp.arange(p)

    def solve_cols(b: jnp.ndarray) -> jnp.ndarray:
        global _TRACE_COUNT
        _TRACE_COUNT += 1  # runs at trace time only
        bx = jnp.concatenate(
            [b.astype(jnp.float32), jnp.zeros((1, width), jnp.float32)], axis=0
        )

        def step(carry, instr):
            x, feedback, rf = carry
            iw, vi = instr
            op, si, ct, sl = decode_instructions(iw, planes)
            ctb = ct[:, None]
            pv = feedback
            slot_val = rf[lanes, sl]  # [p, width]
            # psum control mux (S1/S2 of Fig. 4b)
            pv = jnp.where(ctb == PS_RESET, 0.0, pv)
            pv = jnp.where(ctb == PS_LOAD, slot_val, pv)
            store_val = jnp.where(
                (ctb == PS_STORE_RESET) | (ctb == PS_SWAP), feedback, slot_val
            )
            rf = rf.at[lanes, sl].set(store_val)
            pv = jnp.where(ctb == PS_STORE_RESET, 0.0, pv)
            pv = jnp.where(ctb == PS_SWAP, slot_val, pv)

            v = stream[vi][:, None]
            pv = jnp.where((op == OP_EDGE)[:, None], pv + v * x[si], pv)
            outv = (bx[si] - pv) * v
            # derived out index: FINAL writes x[src], everything else
            # scatters into the dummy row x[n]
            write_idx = jnp.where(op == OP_FINAL, si, n)
            x = x.at[write_idx].set(outv, mode="promise_in_bounds")
            return (x, pv, rf), ()

        x0 = jnp.zeros((n + 1, width), dtype=jnp.float32)
        f0 = jnp.zeros((p, width), dtype=jnp.float32)
        rf0 = jnp.zeros((p, nslots, width), dtype=jnp.float32)
        (x, _, _), _ = jax.lax.scan(step, (x0, f0, rf0), (instr_words, vidx))
        return x[:n]

    return permute_rows(prog, solve_cols)


def permute_rows(prog: Program, solve_cols):
    """Wrap an internal-order ``solve_cols`` so that it takes b and returns
    x in the workload's row order: two row gathers by constant indices,
    traced into the caller's jit under the name scope ``sptrsv.row_perm``
    (in each op's HLO metadata).  A program in its workload's order gets
    ``solve_cols`` back unchanged, so its trace gains nothing."""
    if prog.row_perm is None:
        return solve_cols
    perm = np.asarray(prog.row_perm, np.int32)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.size, dtype=np.int32)

    def rows(a, idx):
        with jax.named_scope("sptrsv.row_perm"):
            return a.at[jnp.asarray(idx)].get(mode="promise_in_bounds",
                                               unique_indices=True)

    def solve_user(b):
        return rows(solve_cols(rows(b, perm)), inv)

    return solve_user


def _build_jax_executor(prog: Program, width: int):
    """Jitted single-device wrapper around `build_solve_cols`."""
    solve_cols = build_solve_cols(prog, width)
    if width == 1:
        # single-RHS form: `solve(b[n]) -> x[n]`, wrap/unwrap inside the jit
        # so the hot path stays one dispatch
        return jax.jit(lambda b: solve_cols(b[:, None])[:, 0])
    return jax.jit(solve_cols)


def _cached_executor(prog: Program, width: int):
    # Cache-key contract (DESIGN.md §4/§9): jax entries are keyed by the
    # *padded* width only — every caller rounds with `pad_batch` before
    # lookup, so batch sizes that pad equal share one trace, and the serve
    # layer's bucket widths (core/serve.py, which buckets with the same
    # `pad_batch`) can never diverge from the cache keys.  An unpadded
    # width reaching this point is a caller bug, not a cache miss.
    if width != pad_batch(width):
        raise AssertionError(
            f"executor cache key must be a padded width "
            f"(pad_batch({width}) == {pad_batch(width)}), got {width}")
    per_prog = _EXEC_CACHE.get(prog)
    if per_prog is None:
        per_prog = {}
        _EXEC_CACHE[prog] = per_prog
    fn = per_prog.get(width)
    if fn is None:
        with TraceAnnotation("sptrsv.executor_build"):
            fn = _build_jax_executor(prog, width)
        per_prog[width] = fn
    return fn


def batched_entry(core, n: int, batch: int, width: int, *,
                  single_core: bool = False, place=None):
    """Shared `solver(b[n, batch]) -> x[n, batch]` entry wrapper.

    Validates the shape, pads the batch axis to ``width``, optionally
    places the padded matrix on devices (``place``, the sharded path of
    `core.shard`), calls ``core`` and slices the pad columns back off.
    ``single_core`` marks a width-1 core with the `[n] -> [n]` signature.
    """

    def solve_many(bmat):
        with TraceAnnotation("sptrsv.stage_in"):
            bmat = jnp.asarray(bmat, dtype=jnp.float32)
            if bmat.shape != (n, batch):
                raise ValueError(
                    f"expected b of shape {(n, batch)}, got {bmat.shape}")
            if batch == 0:
                return jnp.zeros((n, 0), jnp.float32)
            if not single_core:
                if batch != width:
                    bmat = jnp.pad(bmat, ((0, 0), (0, width - batch)))
                if place is not None:
                    bmat = place(bmat)
        with TraceAnnotation("sptrsv.dispatch"):
            if single_core:
                return core(bmat[:, 0])[:, None]
            return core(bmat)[:, :batch]

    return solve_many


def make_jax_executor(prog: Program, batch: int | None = None):
    """Build (or fetch from cache) a jitted solve closure for `prog`.

    * ``batch=None`` — `solve(b[n]) -> x[n]`, the classic single-RHS form.
    * ``batch=B``    — `solve(b[n, B]) -> x[n, B]`: one pass over the
      instruction stream solves all B columns.

    The underlying jitted executor is cached per (program identity, padded
    batch width): repeated calls — and batch widths that pad to the same
    width — reuse the trace.
    """
    if batch is None:
        core = _cached_executor(prog, 1)
        n = prog.n

        def solve_one(b):
            # np-side cast (no-copy when already f32) keeps one trace per
            # program regardless of caller dtype; jax arrays and tracers
            # pass through untouched so the closure stays transformable
            if not isinstance(b, jax.Array):
                b = np.asarray(b, np.float32)
            if b.shape != (n,):
                raise ValueError(f"expected b of shape {(n,)}, got {b.shape}")
            return core(b)

        return solve_one

    width = pad_batch(batch)
    core = _cached_executor(prog, width)
    return batched_entry(core, prog.n, batch, width, single_core=width == 1)


def validate_backend(backend: str, backend_opts: dict) -> None:
    """Shared backend-argument check for api/shard solver entry points.

    Rejections use the structured taxonomy (`core.errors`, DESIGN.md §7):
    `UnknownBackendError` for a backend name outside the supported set,
    `BackendOptionsError` for options a backend does not accept.  Both
    also subclass the historical builtin (``ValueError`` / ``TypeError``)
    they replace, so pre-taxonomy callers keep working.
    """
    from .errors import BackendOptionsError, UnknownBackendError

    if backend not in ("jax", "pallas"):
        raise UnknownBackendError(
            f"unknown backend {backend!r} (choose 'jax' or 'pallas')",
            detail={"backend": backend})
    if backend == "jax" and backend_opts:
        raise BackendOptionsError(
            f"backend='jax' takes no extra options, got "
            f"{sorted(backend_opts)}",
            detail={"backend": backend, "options": sorted(backend_opts)})


def make_pallas_executor(
    prog: Program,
    batch: int | None = None,
    *,
    placement: str = "auto",
    vmem_limit_bytes: int | None = None,
    x_block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Build (or fetch from cache) a Pallas-kernel solve closure for `prog`.

    Same calling convention as `make_jax_executor` (``batch=None`` ->
    ``solve(b[n]) -> x[n]``; ``batch=B`` -> ``solve(b[n, B]) -> x[n, B]``)
    but executing `repro.kernels.sptrsv` instead of the `lax.scan` program.

    ``placement`` selects the kernel's memory regime: ``"resident"`` keeps
    x VMEM-resident, ``"blocked"`` forces the HBM-resident row-window path
    (large n), ``"auto"`` goes blocked where x does not fit the solve-state
    budget (a core's VMEM less Mosaic's headroom, or ``vmem_limit_bytes``)
    and a smaller window exists (see
    `repro.kernels.sptrsv.ops.resolve_placement`).
    Executors are cached per (program identity, padded batch width, all
    placement knobs, interpret) — the window plan and the staged
    instruction tensors are computed once per cache entry, so repeated
    solves never re-stage or retrace.
    """
    from repro.kernels.sptrsv import ops as sptrsv_ops  # lazy: ops imports us

    width = pad_batch(batch if batch is not None else 1)
    key = ("pallas", width, placement, vmem_limit_bytes, x_block_rows,
           interpret)
    per_prog = _EXEC_CACHE.get(prog)
    if per_prog is None:
        per_prog = {}
        _EXEC_CACHE[prog] = per_prog
    core = per_prog.get(key)
    if core is None:
        try:
            with TraceAnnotation("sptrsv.executor_build"):
                core = sptrsv_ops.build_solver_cols(
                    prog, width, placement=placement,
                    vmem_limit_bytes=vmem_limit_bytes,
                    x_block_rows=x_block_rows, interpret=interpret,
                )
        except Exception as e:
            # surface kernel/staging construction failures as the taxonomy
            # (DESIGN.md §7) so the fallback ladder can classify and
            # degrade; taxonomy leaves (e.g. an infeasible placement) pass
            # through untouched
            from .errors import BackendExecutionError, RobustnessError

            if isinstance(e, RobustnessError):
                raise
            raise BackendExecutionError(
                f"pallas solver construction failed "
                f"({type(e).__name__}: {e})",
                detail={"placement": placement, "width": width}) from e
        per_prog[key] = core
    n = prog.n
    if batch is None:
        def solve_one(b):
            b = jnp.asarray(b, jnp.float32)
            if b.shape != (n,):
                raise ValueError(f"expected b of shape {(n,)}, got {b.shape}")
            return core(b[:, None])[:, 0]

        solve = solve_one
    else:
        solve = batched_entry(core, n, batch, width)
    solve.placement = core.placement
    solve.plan = core.plan
    solve.interpret = core.interpret
    solve.stream_words = core.stream_words
    solve.slot_words = core.slot_words
    return solve


def execute_jax(prog: Program, b: np.ndarray) -> np.ndarray:
    """Solve via the cached jax executor; `b` is `[n]` or `[n, B]`."""
    bmat, single = as_batch(b)
    if single:
        x = make_jax_executor(prog)(bmat[:, 0])
    else:
        x = make_jax_executor(prog, batch=bmat.shape[1])(bmat)
    with TraceAnnotation("sptrsv.readback"):
        return np.asarray(x)
