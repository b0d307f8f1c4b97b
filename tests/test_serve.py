"""Deterministic serving tests (DESIGN.md §9): every scheduling branch of
the micro-batcher driven by an injectable clock — no sleeps, no wall
time — plus the `ProgramCache` tier behavior (LRU order, capacity-1
thrash, disk rehydrate, fingerprints, corruption degradation) and the
`BENCH_serve.json` schema / smoke guards for tier-1.
"""

import json

import numpy as np
import pytest

from repro.core import api, executor
from repro.core.errors import ProgramCorruptionError
from repro.core.matrices import generate
from repro.core.serve import (
    FLUSH_DEADLINE,
    FLUSH_DRAIN,
    FLUSH_FULL,
    ManualClock,
    ProgramCache,
    SolveService,
    pattern_fingerprint,
)


@pytest.fixture(scope="module")
def mats():
    return {"a": generate("band_cz"), "b": generate("chem_bp")}


def make_svc(mats, clock, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_delay", 1.0)
    svc = SolveService(ProgramCache(), clock=clock, **kw)
    for mid, m in mats.items():
        svc.register(mid, m)
    return svc


def oracle(svc, mid, b):
    prog = svc.cache.get(svc._mats[mid])
    return np.asarray(api.solve(prog, np.asarray(b, np.float32)))


# ---------------------------------------------------------------- batcher
def test_deadline_flush_not_before_deadline(mats):
    clock = ManualClock()
    svc = make_svc(mats, clock)
    b = np.random.default_rng(0).standard_normal(mats["a"].n)
    t = svc.submit("a", b)
    assert not t.done
    clock.advance(0.999)
    assert svc.pump() == 0 and not t.done
    clock.advance(0.001)  # deadline is inclusive: arrival + max_delay <= now
    assert svc.pump() == 1 and t.done
    assert svc.stats.flushes_deadline == 1 and svc.stats.flushes_full == 0
    assert svc.stats.flushes[0].reason == FLUSH_DEADLINE
    np.testing.assert_array_equal(t.result(), oracle(svc, "a", b))


def test_bucket_full_flush_is_immediate_no_clock_motion(mats):
    clock = ManualClock()
    svc = make_svc(mats, clock)
    rng = np.random.default_rng(1)
    bs = [rng.standard_normal(mats["a"].n) for _ in range(4)]
    tickets = [svc.submit("a", b) for b in bs]
    assert all(t.done for t in tickets)  # 4th submit filled the bucket
    assert svc.stats.flushes_full == 1 and svc.stats.flushes_deadline == 0
    rec = svc.stats.flushes[0]
    assert (rec.reason, rec.columns, rec.padded) == (FLUSH_FULL, 4, 8)
    for t, b in zip(tickets, bs):
        np.testing.assert_array_equal(t.result(), oracle(svc, "a", b))


def test_out_of_order_completion_across_matrices(mats):
    clock = ManualClock()
    svc = make_svc(mats, clock)
    rng = np.random.default_rng(2)
    slow = svc.submit("a", rng.standard_normal(mats["a"].n))
    fast = [svc.submit("b", rng.standard_normal(mats["b"].n))
            for _ in range(4)]
    # matrix b's bucket filled and flushed although submitted later
    assert all(t.done for t in fast) and not slow.done
    clock.advance(1.0)
    svc.pump()
    assert slow.done
    assert slow.completed_at == 1.0 and fast[0].completed_at == 0.0


def test_deadline_order_is_deterministic_oldest_first(mats):
    clock = ManualClock()
    svc = make_svc(mats, clock)
    rng = np.random.default_rng(3)
    ta = svc.submit("a", rng.standard_normal(mats["a"].n))
    clock.advance(0.5)
    tb = svc.submit("b", rng.standard_normal(mats["b"].n))
    clock.advance(1.0)  # both due; a (older) must flush first
    assert svc.pump() == 2
    assert ta.done and tb.done
    assert [f.matrix_id for f in svc.stats.flushes] == ["a", "b"]


def test_submit_pumps_due_buckets_before_enqueueing(mats):
    clock = ManualClock()
    svc = make_svc(mats, clock)
    rng = np.random.default_rng(4)
    old = svc.submit("a", rng.standard_normal(mats["a"].n))
    clock.advance(5.0)
    new = svc.submit("a", rng.standard_normal(mats["a"].n))
    # the overdue bucket flushed (deadline) before the new arrival joined
    assert old.done and not new.done
    assert svc.stats.flushes[0].columns == 1


def test_wide_request_spans_flushes_and_routes_all_columns(mats):
    clock = ManualClock()
    svc = make_svc(mats, clock)
    n = mats["a"].n
    bmat = np.random.default_rng(5).standard_normal((n, 10))
    t = svc.submit("a", bmat)
    # two immediate full flushes of 4, two columns left pending
    assert not t.done and svc.pending_columns("a") == 2
    assert svc.stats.flushes_full == 2
    assert svc.drain() == 1
    assert t.done and t.flush_indices == [0, 1, 2]
    assert svc.stats.flushes[2].reason == FLUSH_DRAIN
    got = t.result()
    assert got.shape == (n, 10)
    for j in range(10):
        np.testing.assert_array_equal(got[:, j], oracle(svc, "a", bmat[:, j]))


def test_per_request_result_routing_distinct_rhs(mats):
    clock = ManualClock()
    svc = make_svc(mats, clock, max_batch=8)
    rng = np.random.default_rng(6)
    bs = [rng.standard_normal(mats["b"].n) for _ in range(8)]
    tickets = [svc.submit("b", b) for b in bs]
    for t, b in zip(tickets, bs):
        np.testing.assert_array_equal(t.result(), oracle(svc, "b", b))


def test_zero_column_request_completes_immediately(mats):
    svc = make_svc(mats, ManualClock())
    t = svc.submit("a", np.zeros((mats["a"].n, 0)))
    assert t.done and t.result().shape == (mats["a"].n, 0)
    assert svc.pending_columns() == 0


def test_submit_errors(mats):
    svc = make_svc(mats, ManualClock())
    with pytest.raises(KeyError, match="unknown matrix_id"):
        svc.submit("nope", np.zeros(4))
    with pytest.raises(ValueError, match="expected b of shape"):
        svc.submit("a", np.zeros(mats["a"].n + 1))
    with pytest.raises(ValueError, match="already registered"):
        svc.register("a", mats["a"])
    t = svc.submit("a", np.zeros(mats["a"].n))
    with pytest.raises(RuntimeError, match="pump\\(\\) or drain\\(\\)"):
        t.result()


def test_core_never_reads_wall_clock(mats):
    calls = []

    def clock():
        calls.append(1)
        return 0.0

    svc = make_svc(mats, clock)
    svc.submit("a", np.zeros(mats["a"].n), now=0.0)
    svc.pump(now=2.0)
    svc.drain(now=3.0)
    # explicit `now=` short-circuits the clock entirely; the default
    # clock is only consulted when no time is passed
    assert calls == []
    svc.submit("a", np.zeros(mats["a"].n))
    assert len(calls) == 1


def test_numpy_backend_and_servestats(mats):
    svc = make_svc(mats, ManualClock(), backend="numpy")
    rng = np.random.default_rng(7)
    before = executor.trace_count()
    bs = [rng.standard_normal(mats["a"].n) for _ in range(4)]
    tickets = [svc.submit("a", b) for b in bs]
    assert executor.trace_count() == before  # numpy path never traces
    prog = svc.cache.get(svc._mats["a"])
    for t, b in zip(tickets, bs):
        np.testing.assert_array_equal(t.result(), api.solve_numpy(prog, b))
    st = svc.stats
    assert (st.requests, st.columns, st.completed_columns) == (4, 4, 4)
    assert st.batched_columns == 4 and st.solver_calls == 1
    assert st.cache["entries"]  # per-entry counters surfaced
    d = st.to_dict()
    assert d["flushes"][0]["reason"] == FLUSH_FULL
    assert json.dumps(d)  # machine-readable end to end


def test_service_arg_validation(mats):
    with pytest.raises(ValueError, match="max_batch"):
        SolveService(max_batch=0)
    with pytest.raises(ValueError, match="max_delay"):
        SolveService(max_delay=-1.0)
    with pytest.raises(ValueError, match="numpy"):
        SolveService(backend="numpy", mesh=object())
    with pytest.raises(ValueError):
        SolveService(backend="bogus")


# ------------------------------------------------------ executor contract
def test_executor_cache_key_contract_asserted(mats):
    prog = ProgramCache().get(mats["a"])
    with pytest.raises(AssertionError, match="padded width"):
        executor._cached_executor(prog, 3)  # 3 is not a padded width
    executor.make_jax_executor(prog, batch=3)  # pads to 8 internally
    entries = executor.cached_entries(prog)
    assert entries and all(
        w == executor.pad_batch(w) for w in entries if isinstance(w, int))


def test_service_buckets_only_create_padded_cache_keys(mats):
    svc = make_svc(mats, ManualClock(), max_batch=5)
    rng = np.random.default_rng(8)
    for _ in range(7):
        svc.submit("a", rng.standard_normal(mats["a"].n))
    svc.drain()
    prog = svc.cache.get(svc._mats["a"])
    widths = [w for w in executor.cached_entries(prog) if isinstance(w, int)]
    assert widths and all(w == executor.pad_batch(w) for w in widths)


# ---------------------------------------------------------- program cache
def _pattern_variant(mat, seed):
    """Same shape/nnz as ``mat``, different pattern (one edge moved)."""
    from repro.core.csr import from_coo

    rng = np.random.default_rng(seed)
    rows, cols, vals = [], [], []
    for i in range(mat.n):
        lo, hi = mat.rowptr[i], mat.rowptr[i + 1]
        for j in range(lo, hi - 1):
            rows.append(i)
            cols.append(int(mat.colidx[j]))
            vals.append(float(mat.values[j]))
    # move one off-diagonal edge to a different column
    for k in range(len(cols)):
        i, c = rows[k], cols[k]
        options = [c2 for c2 in range(i) if c2 != c and
                   c2 not in [cols[q] for q in range(len(cols))
                              if rows[q] == i]]
        if options:
            cols[k] = int(rng.choice(options))
            break
    diag = np.asarray([float(mat.values[mat.rowptr[i + 1] - 1])
                       for i in range(mat.n)])
    return from_coo(mat.n, np.asarray(rows), np.asarray(cols),
                    np.asarray(vals), diag, name=mat.name + "_variant")


def test_fingerprint_structure_only_and_distinguishes_patterns(mats):
    m = mats["a"]
    fp = pattern_fingerprint(m)
    # same pattern, different values -> same fingerprint
    import dataclasses

    m2 = dataclasses.replace(m, values=m.values * 2.0)
    assert pattern_fingerprint(m2) == fp
    # same shape, different pattern -> different fingerprint
    m3 = _pattern_variant(m, 0)
    assert m3.n == m.n and m3.nnz == m.nnz
    assert pattern_fingerprint(m3) != fp


def test_lru_eviction_order_and_hits():
    a, b, c = generate("band_cz"), generate("chem_bp"), generate("ckt_fpga")
    cache = ProgramCache(capacity=2)
    pa, pb = cache.get(a), cache.get(b)
    assert cache.fingerprints() == [pattern_fingerprint(a),
                                    pattern_fingerprint(b)]
    assert cache.get(a) is pa  # hit refreshes recency: order now [b, a]
    cache.get(c)               # evicts b (least recently used)
    assert cache.fingerprints() == [pattern_fingerprint(a),
                                    pattern_fingerprint(c)]
    assert cache.evictions == 1
    assert cache.get(b) is not pb  # b was evicted -> recompiled object
    ent = cache.entries[pattern_fingerprint(b)]
    assert ent.compiles == 2 and ent.hits == 0
    ea = cache.entries[pattern_fingerprint(a)]
    assert ea.hits == 1 and ea.compiles == 1
    assert ea.compile_seconds > 0.0


def test_capacity_one_thrash_memory_only():
    a, b = generate("band_cz"), generate("chem_bp")
    cache = ProgramCache(capacity=1)
    for _ in range(2):
        cache.get(a)
        cache.get(b)
    assert len(cache) == 1 and cache.evictions == 3
    assert cache.entries[pattern_fingerprint(a)].compiles == 2
    assert cache.entries[pattern_fingerprint(b)].compiles == 2
    assert cache.hits == 0 and cache.misses == 4


def test_capacity_one_thrash_disk_tier_rehydrates(tmp_path):
    a, b = generate("band_cz"), generate("chem_bp")
    cache = ProgramCache(capacity=1, disk_dir=tmp_path)
    for _ in range(3):
        cache.get(a)
        cache.get(b)
    # one compile each; every revisit rehydrated from disk, no recompile
    ea = cache.entries[pattern_fingerprint(a)]
    eb = cache.entries[pattern_fingerprint(b)]
    assert (ea.compiles, eb.compiles) == (1, 1)
    assert (ea.disk_hits, eb.disk_hits) == (2, 2)


def test_disk_rehydrate_equals_in_memory_program(tmp_path):
    a = generate("band_cz")
    cache = ProgramCache(capacity=1, disk_dir=tmp_path)
    pa = cache.get(a)
    cache.get(generate("chem_bp"))  # evict a
    ra = cache.get(a)               # rehydrated from disk
    assert ra is not pa
    assert ra.n == pa.n and ra.num_slots == pa.num_slots
    assert ra.config == pa.config
    np.testing.assert_array_equal(ra.instr, pa.instr)
    np.testing.assert_array_equal(ra.val_idx, pa.val_idx)
    np.testing.assert_array_equal(ra.stream, pa.stream)
    rng = np.random.default_rng(9)
    bb = rng.standard_normal(a.n)
    np.testing.assert_array_equal(np.asarray(api.solve(ra, bb)),
                                  np.asarray(api.solve(pa, bb)))


def test_corrupt_disk_entry_degrades_to_recompile_with_incident(tmp_path):
    a = generate("band_cz")
    cache = ProgramCache(capacity=1, disk_dir=tmp_path)
    cache.get(a)
    blobs = list(tmp_path.glob("*.prog"))
    assert len(blobs) == 1
    raw = bytearray(blobs[0].read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    blobs[0].write_bytes(bytes(raw))
    cache.get(generate("chem_bp"))  # evict a from memory
    prog = cache.get(a)             # corrupt blob -> incident + recompile
    ent = cache.entries[pattern_fingerprint(a)]
    assert ent.disk_corrupt == 1 and ent.compiles == 2
    inc = cache.incidents[-1]
    assert inc.stage == "program-cache" and inc.kind == "disk-corrupt"
    assert inc.error == "ProgramCorruptionError"
    b = np.random.default_rng(10).standard_normal(a.n)
    np.testing.assert_allclose(np.asarray(api.solve(prog, b)),
                               api.reference_solve(a, b),
                               rtol=1e-4, atol=1e-4)
    # the rewritten blob is healthy again
    assert cache.get(generate("chem_bp")) is not None
    assert cache.get(a) is not prog
    assert ent.disk_corrupt == 1  # no further corruption events


def test_same_pattern_new_values_is_a_values_refresh(tmp_path):
    import dataclasses

    a = generate("band_cz")
    a2 = dataclasses.replace(a, values=a.values * 1.5)
    cache = ProgramCache(capacity=2, disk_dir=tmp_path)
    p1 = cache.get(a)
    p2 = cache.get(a2)  # same fingerprint, different values CRC
    assert p1 is not p2  # new identity: executors cache on identity
    fp = pattern_fingerprint(a)
    # guarded miss served by the values-only fast path: one compiler run,
    # the second program regathered through the provenance plane
    assert cache.entries[fp].compiles == 1
    assert cache.entries[fp].value_refreshes == 1
    assert cache.misses == 2 and cache.value_refreshes == 1
    # schedule tensors shared, value stream fresh
    assert p2.instr is p1.instr and p2.stream is not p1.stream
    assert len(list(tmp_path.glob(f"{fp}.*.prog"))) == 2  # distinct blobs
    b = np.random.default_rng(11).standard_normal(a.n)
    np.testing.assert_allclose(np.asarray(api.solve(p2, b)),
                               api.reference_solve(a2, b),
                               rtol=1e-4, atol=1e-4)
    # the refreshed stream is bit-identical to a full recompile's
    from repro.core.compiler import compile_program

    np.testing.assert_array_equal(p2.stream, compile_program(a2).stream)


def test_values_refresh_disk_blob_rehydrates(tmp_path):
    import dataclasses

    a = generate("band_cz")
    a2 = dataclasses.replace(a, values=a.values * 2.0)
    cache = ProgramCache(capacity=2, disk_dir=tmp_path)
    cache.get(a)
    cache.get(a2)
    # a fresh cache finds both blobs on disk: zero compiles, zero refreshes
    cold = ProgramCache(capacity=2, disk_dir=tmp_path)
    cold.get(a2)
    fp = pattern_fingerprint(a)
    assert cold.entries[fp].compiles == 0
    assert cold.entries[fp].disk_hits == 1


def test_cache_rejects_zero_capacity():
    with pytest.raises(ValueError, match="capacity"):
        ProgramCache(capacity=0)


def test_load_program_corruption_error_type(tmp_path):
    path = tmp_path / "junk.prog"
    path.write_bytes(b"not a program")
    with pytest.raises(ProgramCorruptionError):
        api.load_program(path)


# ------------------------------------------------------- api.make_service
def test_make_service_defaults_and_disk_tier(tmp_path, mats):
    clock = ManualClock()
    svc = api.make_service(mats, capacity=1, disk_dir=tmp_path,
                           max_batch=2, max_delay=0.5, clock=clock)
    rng = np.random.default_rng(12)
    ta = svc.submit("a", rng.standard_normal((mats["a"].n, 2)))
    tb = svc.submit("b", rng.standard_normal((mats["b"].n, 2)))
    assert ta.done and tb.done
    # capacity-1 cache spilled "a" to disk; next "a" flush rehydrates
    tc = svc.submit("a", rng.standard_normal(mats["a"].n))
    clock.advance(0.5)
    svc.pump()
    assert tc.done
    fp = pattern_fingerprint(mats["a"])
    assert svc.cache.entries[fp].disk_hits == 1
    assert svc.cache.entries[fp].compiles == 1


# ------------------------------------------------- bench smoke + schema
def test_serve_load_smoke(capsys):
    from benchmarks.serve_load import main

    main(["--smoke"])
    out = capsys.readouterr().out
    assert "smoke" in out


def test_bench_serve_json_schema():
    from scripts.check_bench import check

    problems = check()
    assert problems == [], "\n".join(problems)
