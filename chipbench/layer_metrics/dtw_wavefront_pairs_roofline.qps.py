"""Share of its roofline that the DTW re-rank kernel reached in the
traced window, in %: the least time the window's DTW work could take on
the chip (the larger of its operations over the bf16 peak and its bytes
over the HBM peak, ``chipbench/work/dtw_wavefront_pairs.py``) over the
device time of the ``jit_dtw_wavefront_pairs`` programs.  The work is
the pairs the cascade left for DTW (``n_candidates``), the seeded pairs
evaluated before it not counted."""
from chipbench import reduce_trace


def read(ctx):
    tr = ctx["trace"]
    pairs = sum(ctx["counters"]["n_candidates"])
    if not tr or not pairs:
        return None
    work = ctx["work"]("dtw_wavefront_pairs")
    seconds = reduce_trace.kernel_seconds(tr, work.MODULE, work.OP_PATTERN)
    if seconds <= 0:
        raise ValueError("the trace holds no jit_dtw_wavefront_pairs op "
                         f"though {pairs} pairs were re-ranked")
    cfg = ctx["config"]
    ops, nbytes = work.work(pairs, int(cfg["dataset"]["length"]),
                            int(cfg["search"]["band"]))
    peaks = ctx["peaks"]
    t_ops, t_bytes = ops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"]
    ctx["log"](f"dtw_wavefront_pairs: {pairs} pairs in {seconds:.6f} s, "
               f"bound by {'operations' if t_ops >= t_bytes else 'bytes'}")
    return 100.0 * max(t_ops, t_bytes) / seconds
