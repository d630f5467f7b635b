"""Work of the batched probe kernel (``kernels.collision_count_batch``).

Per batch the probe compares every query shift's signature with every
stored row: it has to read the stored signatures, ``rows * K`` int32
values, once.  That read is the kernel's work; the comparisons are
integer operations far below any peak, so the memory bound applies.
"""

MODULE = "collision_count_batch"    # the jitted program, jit_<MODULE>
OP_PATTERN = None
BYTES_PER_VALUE = 4


def work(batches: int, rows: int, num_hashes: int):
    """(operations, bytes) of ``batches`` probes over the stored rows."""
    return 0, batches * rows * num_hashes * BYTES_PER_VALUE
