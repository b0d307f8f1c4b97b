"""Benchmark harness driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig9a ...  # subset

Each module prints a CSV (also saved to results/bench/) whose rows carry
``name,<metrics>``; wall-clock entries are reported as ``*_us_per_call``.
"""

from __future__ import annotations

import sys
import time

from . import (
    analysis_overhead,
    batched_rhs,
    compiler_scaling,
    dag_workloads,
    large_n,
    node_splitting,
    dataflow_comparison,
    icr_ablation,
    instruction_breakdown,
    platform_comparison,
    psum_sweep,
    robust_overhead,
    schedule_frontier,
    serve_chaos,
    serve_load,
    sharded_batch,
    suite_stats,
)

MODULES = {
    "fig9a": dataflow_comparison,
    "fig9bc": psum_sweep,
    "fig9def": icr_ablation,
    "fig10": instruction_breakdown,
    "fig11": platform_comparison,
    "table3": suite_stats,
    "table4": compiler_scaling,
    "beyond": node_splitting,
    "batched": batched_rhs,
    "sharded": sharded_batch,
    "large_n": large_n,
    "dagwork": dag_workloads,
    "robust": robust_overhead,
    "analysis": analysis_overhead,
    "serve": serve_load,
    "chaos": serve_chaos,
    "frontier": schedule_frontier,
}


def main() -> None:
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    wanted = sys.argv[1:] or list(MODULES)
    for key in wanted:
        mod = MODULES[key]
        print(f"\n===== {key}: {mod.__doc__.splitlines()[0]} =====")
        t0 = time.perf_counter()
        mod.main()
        print(f"# {key} done in {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    main()
