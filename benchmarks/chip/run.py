#!/usr/bin/env python3
"""Chip benchmark of the SpTRSV solve path: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs on the machine it is started on and only on a TPU: with another
platform, or fewer chips than the cell asks for, it exits 2 naming what it
found and prints no result.  A run

1. builds the cell's matrix from its configuration and the right-hand
   sides from ``--seed``;
2. compiles it with ``api.compile``;
3. warms up every batch width the window uses;
4. drives the cell's traffic for ``--seconds`` (with ``--trace 1`` under
   the profiler);
5. compares the answers given in the window with the float64 reference;
6. prints the result as the last line of standard output, and each number
   compared beside its limit as the last lines of standard error.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, as ``BENCHMARK.json`` lists them.  JAX's persistent
compilation cache lives at ``<checkout>/.jax_cache`` (or where
``JAX_COMPILATION_CACHE_DIR`` says), so only a checkout's first run of a
cell compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up runs from here to the window's start

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_CHECKOUT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    # run as a script: import this directory as the `benchmarks.chip`
    # package (its modules' names, such as trace, shadow the stdlib's)
    sys.path[0] = str(_CHECKOUT)
sys.path.insert(1, str(_CHECKOUT / "src"))

import numpy as np  # noqa: E402

from benchmarks.chip import reference, registry  # noqa: E402

TRACE_DIR = _CHECKOUT / ".bench_trace"


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell needs."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_tpu(chips: int) -> dict:
    """The devices as JAX reports them; raises `NoChip` off a TPU."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        log(f"JAX found platform {d.platform!r} ({d.device_kind}), not a "
            f"TPU; this benchmark runs only on the chip")
        raise NoChip(2)
    if len(devs) < chips:
        log(f"the cell needs {chips} chips, JAX found {len(devs)}")
        raise NoChip(2)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def enable_cache() -> str:
    import jax

    from repro.core.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    # cache every program, the small pad/slice ones too, so that a
    # checkout's second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_matrix(config: dict):
    """(program matrix, reference) from the configuration's generator."""
    from repro.core.csr import from_coo

    gen = registry.load_code("generators", config["generator"])
    rows, cols, vals, diag = gen.generate(
        **{k: config[k] for k in config["generator_params"]})
    n = config["n"]
    arrays = reference.csr_arrays(n, rows, cols, vals, diag)
    got = reference.fingerprint(*arrays)
    if got != config["fingerprint"]:
        raise ValueError(f"{config['name']}: generated matrix {got} differs "
                         f"from the configuration's {config['fingerprint']}")
    mat = from_coo(n, rows, cols, vals, diag, name=config["name"])
    return mat, reference.Reference(*arrays)


def solve_options(traffic: dict) -> dict:
    """Backend keywords of the solve entry; the Pallas kernel never runs in
    interpret mode here."""
    backend = traffic.get("backend", "pallas")
    opts = {"backend": backend}
    if backend == "pallas":
        opts["interpret"] = False
    return opts


# --------------------------------------------------------------------------
# One class per traffic driver: __init__ compiles, `prepare(seed)` makes
# the seed's inputs and warms up, `window(seconds)` drives the driver,
# `answers(res)` pairs each answer kept with its input.
# --------------------------------------------------------------------------
class Closed:
    """`closed_batch`: ``api.solve_batch`` on ``batch`` columns per call,
    sharded over ``devices`` chips when there are more than one."""

    def __init__(self, cell, mat, spans: dict):
        import jax

        from repro.core import api, shard

        self.api, self.tr = api, cell.traffic
        self.n = mat.n
        self.width = int(self.tr["batch"])
        self.devices = int(self.tr.get("devices", 1))
        self.opts = solve_options(self.tr)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("api.compile"):
            self.program = api.compile(mat)
        spans["compile"] = time.perf_counter() - t
        self.mesh = shard.batch_mesh(self.devices) if self.devices > 1 \
            else None
        if self.mesh is None and self.opts["backend"] == "pallas":
            solver = api.make_solver(self.program, batch=self.width,
                                     **self.opts)
            if solver.interpret is not False:
                raise RuntimeError("the Pallas kernel would run in "
                                   "interpret mode")
        self.driver = registry.load_code("drivers", self.tr["driver"])

    def solve(self, b):
        return self.api.solve_batch(self.program, b, mesh=self.mesh,
                                    **self.opts)

    def prepare(self, seed: int, spans: dict) -> None:
        rng = np.random.default_rng(seed)
        self.seed = seed
        self.pool = [rng.standard_normal((self.n, self.width))
                     .astype(np.float32) for _ in range(int(self.tr["pool"]))]
        t = time.perf_counter()
        for b in self.pool[:2]:  # the first call compiles, the second is warm
            self.solve(b)
        spans["warmup"] = time.perf_counter() - t

    def window(self, seconds: float) -> dict:
        return self.driver.run(self.solve, self.pool, seconds, seed=self.seed,
                               keep=int(self.tr.get("keep", 8)))

    def answers(self, res: dict) -> list:
        return [(self.pool[k], x) for k, x in res["kept"]]


DRIVERS = {"closed_batch": Closed}


# --------------------------------------------------------------------------
def compare(ref: reference.Reference, pairs: list) -> float:
    """Widest relative gap over the answers; one reference solve per
    distinct input."""
    err = 0.0
    done: dict = {}
    for b, x in pairs:
        key = id(b)
        if key not in done:
            done[key] = ref.solve(b)
        err = max(err, reference.rel_err(x, done[key]))
    return err


class Compiles:
    """JAX traces and backend compiles (or compile-cache reads) while
    installed: a window that warm-up covered has none."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self)

    def summary(self) -> str:
        return ", ".join(f"{v} {k}" for k, v in self.counts.items())


def memory_peak(devices: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:devices]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def peak_of(kind: str) -> dict:
    """The device's peaks from ``peaks.json``; an unknown kind is an error."""
    with open(registry.HERE / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"({sorted(table)})")
    return table[kind]


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             device: dict, t_start: float = T_START) -> tuple[dict, dict]:
    """One run of ``cell``; returns (result line, compared numbers)."""
    import jax

    from benchmarks.chip import trace as trace_mod

    peak = peak_of(device["kind"])
    spans = {"init": time.perf_counter() - t_start}
    t = time.perf_counter()
    mat, ref = build_matrix(cell.config)
    spans["generate"] = time.perf_counter() - t
    kind = DRIVERS[cell.traffic["driver"]](cell, mat, spans)
    kind.prepare(seed, spans)
    setup_s = time.perf_counter() - t_start
    log("setup split (s): " + ", ".join(f"{k} {v:.3f}"
                                         for k, v in spans.items()))
    trace_summary = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_mod.start(TRACE_DIR)
    t_window = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"), Compiles() as compiles:
        res = kind.window(seconds)
    if trace:
        trace_mod.stop()
        trace_summary = trace_mod.summarize(
            trace_mod.load(TRACE_DIR), devices=kind.devices,
            window_span="window")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    traced_wall = time.perf_counter() - t_window
    log(f"in the window: {compiles.summary()}")
    mem = memory_peak(kind.devices)
    pairs = kind.answers(res)
    ctx = dict(cell=cell, window=res, setup_s=setup_s, spans=spans, peak=peak,
               trace=trace_summary, program_stats=kind.program.stats,
               matrix=(mat.n, mat.nnz), width=kind.width,
               devices=kind.devices)
    t = time.perf_counter()
    err = compare(ref, pairs) if pairs else float("inf")
    log(f"reference compared {len(pairs)} answers in "
        f"{time.perf_counter() - t:.3f} s")
    limit = float(cell.config["limit_max_rel_err"])
    checks = {"max_rel_err": {"value": err, "limit": limit},
              "failed": {"value": res["failed"], "limit": 0}}
    correct = bool(pairs) and res["failed"] == 0 and err <= limit
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = registry.load_code("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=mem)
    out = {"correct": correct, "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics, "device": dev}
    if trace_summary is not None:
        dev["busy_s"] = trace_summary["busy_s"]
        dev["window_s"] = trace_summary["window_s"]
        out["breakdown"] = trace_summary["breakdown"]
        log(f"traced window {traced_wall:.3f} s wall")
    if "call_s" in res and res["call_s"]:
        calls = np.asarray(res["call_s"])
        med = np.median(calls)
        log(f"call ms: median {med * 1e3:.4f}, max {calls.max() * 1e3:.4f}; "
            f"{int((calls > 10 * med).sum())} calls over 10x the median")
    for e in res.get("errors", []):
        log(f"call failed: {e}")
    out["checks"] = checks
    return out, checks


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    try:
        device = require_tpu(cell.chips)
    except NoChip as e:
        return int(e.code)
    log(f"compile cache {enable_cache()}")
    out, checks = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           device)
    print(json.dumps(out), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
