"""Closed loop: one caller solves a batch, waits for the answer, repeats.

The caller is a Krylov or multi-load-case user: each call hands ``B``
right-hand-side columns to the solve entry and blocks until the solution is
back on the host.  Calls cycle through a pool of distinct seeded inputs
made in set-up.  The window is the time from the first call's start to the
last call's end; every call started in it counts, the last one included.
"""

from __future__ import annotations

import random
import time

from jax.profiler import TraceAnnotation


def run(solve, pool: list, seconds: float, *, seed: int, keep: int = 8,
        clock=time.perf_counter) -> dict:
    """Call ``solve(pool[i % len(pool)])`` until ``seconds`` have passed.

    ``solve`` returns the host solution.  A uniform sample of ``keep``
    answers (reservoir, drawn from ``seed``) is kept for the comparison
    with the reference as ``(pool index, answer)``; each call's seconds
    are kept for the log.
    """
    rng = random.Random(seed)
    kept: list = []
    call_s: list = []
    calls = failed = 0
    errors: list = []
    t0 = clock()
    deadline = t0 + seconds
    while True:
        k = calls % len(pool)
        t = clock()
        try:
            with TraceAnnotation("solve_batch"):
                x = solve(pool[k])
        except Exception as e:  # a failed call counts against `correct`
            failed += 1
            errors.append(f"{type(e).__name__}: {e}")
            x = None
        call_s.append(clock() - t)
        calls += 1
        if x is not None:
            if len(kept) < keep:
                kept.append((k, x))
            else:
                j = rng.randrange(calls)
                if j < keep:
                    kept[j] = (k, x)
        now = clock()
        if now >= deadline:
            break
    return {"attempted": calls, "failed": failed, "errors": errors[:3],
            "window_s": now - t0, "kept": kept, "call_s": call_s,
            "columns": (calls - failed) * pool[0].shape[1]}
