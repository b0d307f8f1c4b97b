"""Circuit-Jacobian generator: a copy of the program's
``core.matrices.circuit`` (add20 / rajat / fpga archetype).

Kept with the benchmark so that the yardstick does not move when the
program's own generators change.  Same draw order as the original, so the
same parameters give the same arrays.
"""

from __future__ import annotations

import numpy as np


def generate(n: int, hubs: int, avg_deg: float, pattern_seed: int):
    """A few hub columns among the first n/8 rows, consumed by many rows
    (power-law fan-out), plus sparse filler within 5% of n behind each row.

    Returns ``(rows, cols, vals, diag)``: int64, int64, float64, float64.
    """
    rng = np.random.default_rng(pattern_seed)
    hub_ids = np.sort(rng.choice(np.arange(n // 8), size=hubs, replace=False))
    rows, cols = [], []
    for i in range(1, n):
        deg = 1 + rng.poisson(max(avg_deg - 1.0, 0.1))
        picked = set()
        for _ in range(deg):
            if rng.random() < 0.45:
                h = hub_ids[rng.integers(len(hub_ids))]
                if h < i:
                    picked.add(int(h))
            else:
                span = max(1, min(i, int(n * 0.05)))
                picked.add(int(i - 1 - rng.integers(span)))
        picked.discard(i)
        for j in sorted(picked):
            rows.append(i)
            cols.append(j)
    vals = rng.uniform(-0.5, 0.5, size=len(rows))
    diag = rng.uniform(1.0, 2.0, size=n) * rng.choice([-1.0, 1.0], size=n)
    return (np.asarray(rows, np.int64), np.asarray(cols, np.int64),
            np.asarray(vals, np.float64), np.asarray(diag, np.float64))
