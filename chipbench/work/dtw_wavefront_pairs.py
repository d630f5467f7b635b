"""Work of the banded DTW re-rank kernel (``kernels.dtw_wavefront_pairs``).

Counted from the algorithm's shapes, not from the kernel's padding: one
(query, candidate) pair of length ``m`` under Sakoe-Chiba radius ``r``
fills ``m * (2r + 1) - r * (r + 1)`` DP cells.  Each cell is
``(q_i - x_j)^2 + min(diag, up, left)``: a subtract, a multiply, two
minimums and an add, 5 operations.  Each pair reads its query and its
candidate once (float32) and writes one distance.
"""

MODULE = "dtw_wavefront_pairs"      # the jitted program, jit_<MODULE>
OP_PATTERN = None                   # every op of that program counts
OPS_PER_CELL = 5
BYTES_PER_VALUE = 4


def cells(m: int, band: int) -> int:
    r = min(int(band), int(m) - 1)
    return int(m) * (2 * r + 1) - r * (r + 1)


def work(pairs: int, m: int, band: int):
    """(operations, bytes) of ``pairs`` DTW evaluations."""
    ops = pairs * cells(m, band) * OPS_PER_CELL
    nbytes = pairs * (2 * m + 1) * BYTES_PER_VALUE
    return ops, nbytes
