"""`work.py` against counts made by hand."""

from benchmarks.chip import work

# 4x4 lower-triangular matrix, nnz = 4 diagonal + 3 off-diagonal = 7:
#   [d . . .]
#   [a d . .]
#   [. b d .]
#   [c . . d]
N, NNZ = 4, 7


def test_bytes_by_hand():
    # values+indices 7 * 8 = 56, rowptr 5 * 4 = 20, b and x 4 rows * 2 cols
    # * (4 + 4) = 64
    assert work.solve_bytes(N, NNZ, 2) == 56 + 20 + 64


def test_flops_by_hand():
    # per column: 3 multiply-adds (6) + 4 divides = 10 = 2 * 7 - 4
    assert work.solve_flops(N, NNZ, 1) == 10
    assert work.solve_flops(N, NNZ, 3) == 30


def test_least_seconds_picks_the_binding_bound():
    peak = {"hbm_bytes_per_s": 100.0, "flops_per_s": 1e9}
    t, bound = work.least_seconds(N, NNZ, 2, peak)
    assert bound == "memory" and t == 140 / 100.0
    t, bound = work.least_seconds(N, NNZ, 2, {"hbm_bytes_per_s": 1e12,
                                               "flops_per_s": 1.0})
    assert bound == "compute" and t == 20.0
