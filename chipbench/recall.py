#!/usr/bin/env python3
"""One-off recall@k of a cell's served answers against exact DTW.

    python3 chipbench/recall.py --workload <cell> --seed <n> --queries 5

The cell's set-up, then ``--queries`` pool queries served through the
engine; then, with the program's state freed, the exact top-k of each
by banded DTW over every archive row (``chipbench.reference``'s plain
recurrence, in row chunks).  Recall is measured once for ``PERF.md``;
the benchmark's runs do not pay for it.
"""
import time

CLOCK0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import harness, spec  # noqa: E402


def exact_topk(ref, archive, q: np.ndarray, k: int, chunk: int):
    n = int(archive.shape[0])
    d = np.concatenate([
        ref.dtw(np.repeat(q[None], min(chunk, n - lo), 0),
                archive[lo:lo + chunk])
        for lo in range(0, n, chunk)])
    return np.argsort(d, kind="stable")[:k], np.sort(d)[:k]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Recall@k against exact DTW.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, default=5)
    ap.add_argument("--chunk", type=int, default=65536)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        harness.look_for_chip(cell.chips)
    except harness.NoChip as e:
        harness.warn(f"recall: {e}")
        return 2
    harness.enable_cache(cell.root)
    from chipbench.reference import Reference
    setup = harness.Setup(cell, args.seed)
    rng = np.random.default_rng([args.seed, 3])
    picks = rng.choice(len(setup.pool), args.queries, replace=False)
    futs = [setup.db.submit(setup.pool[i]) for i in picks]
    served = [f.result(timeout=600) for f in futs]
    setup.close()
    cfg = cell.config
    ref = Reference(cfg["encoder"], cfg["search"])
    k = int(cfg["search"]["topk"])
    rows = []
    for i, res in zip(picks, served):
        t = time.perf_counter()
        ids, d = exact_topk(ref, setup.archive, setup.pool[i], k,
                            args.chunk)
        hit = len(set(ids.tolist()) & set(np.asarray(res.ids).tolist()))
        rows.append({"query": int(i), "recall": hit / k,
                     "exact_kth": float(d[-1]),
                     "served_kth": float(np.asarray(res.dists)[-1]),
                     "seconds": time.perf_counter() - t})
        harness.log("recall: " + json.dumps(rows[-1]))
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "k": k, "recall_mean": float(np.mean(
                          [r["recall"] for r in rows])), "rows": rows}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
