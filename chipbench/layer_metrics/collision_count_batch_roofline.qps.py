"""Share of its roofline that the batched probe kernel reached in the
traced window, in %: the stored signatures each batch's probe has to
read (``chipbench/work/collision_count_batch.py``) over the HBM peak,
over the device time of the ``jit_collision_count_batch`` programs."""
from chipbench import reduce_trace


def read(ctx):
    tr = ctx["trace"]
    batches = ctx["counters"]["batches"]
    if not tr or not batches:
        return None
    work = ctx["work"]("collision_count_batch")
    seconds = reduce_trace.kernel_seconds(tr, work.MODULE, work.OP_PATTERN)
    if seconds <= 0:
        raise ValueError("the trace holds no jit_collision_count_batch op "
                         f"though {batches} batches were probed")
    cfg = ctx["config"]
    _, nbytes = work.work(batches, int(cfg["dataset"]["rows"]),
                          int(cfg["encoder"]["num_hashes"]))
    ctx["log"](f"collision_count_batch: {batches} batches in "
               f"{seconds:.6f} s")
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_s"] / seconds
