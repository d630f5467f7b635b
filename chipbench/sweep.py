#!/usr/bin/env python3
"""Find an open-loop cell's knee: one set-up, then a window per rate.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 4,8,12,16

Each rate is offered as the cell's traffic with its ``rate_qps``
replaced.  A rate is sustained when the window completes at least 0.9
of it (the ``repro.loadgen.harness.sweep`` rule) and the backlog does
not grow: the median latency of the window's last third of requests is
at most twice that of its first third.
The knee is the highest sustained rate; the cell's traffic file then
holds 0.8 of it.  Prints one line per rate and a JSON summary last.
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

SUSTAINED_FRAC = 0.9


def sustained(row: dict) -> bool:
    return (row["achieved_qps"] >= SUSTAINED_FRAC * row["offered_qps"]
            and row["p50_last_third_ms"] <= 2.0 * row["p50_first_third_ms"]
            and row["failed"] == 0)


def sweep(setup, rates, seconds: float, seed: int):
    rows = []
    for i, rate in enumerate(rates):
        res, counters = harness.run_window(setup, seconds, seed + i,
                                           rate_qps=rate)
        lat = res.latencies_ms()
        third = max(1, len(lat) // 3)
        last = max((d for d in res.done_at if d is not None), default=0.0)
        row = {"offered_qps": res.attempted / seconds,
               "achieved_qps": res.completed_in_window() / seconds,
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "p99_ms": float(np.percentile(lat, 99)),
               "p50_first_third_ms": float(np.percentile(lat[:third], 50)),
               "p50_last_third_ms": float(np.percentile(lat[-third:], 50)),
               "drain_ms": max(0.0, last - seconds) * 1e3,
               "failed": res.failed,
               "batch_size_mean": (sum(k * v for k, v in
                                       counters["batch_sizes"].items())
                                   / max(1, counters["batches"]))}
        row["sustained"] = sustained(row)
        harness.log("sweep: " + " ".join(
            f"{k}={v!r}" for k, v in row.items()))
        rows.append(row)
    ok = [r["offered_qps"] for r in rows if r["sustained"]]
    return rows, (max(ok) if ok else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Knee sweep of one cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        sys.exit("sweep: only open-loop cells have a knee")
    try:
        harness.look_for_chip(cell.chips)
    except harness.NoChip as e:
        harness.warn(f"sweep: {e}")
        return 2
    harness.enable_cache(cell.root)
    setup = harness.Setup(cell, args.seed)
    harness.log(f"sweep: setup_s={time.perf_counter() - CLOCK0:.3f} "
                f"build_s={setup.build_s:.3f}")
    rates = [float(r) for r in args.rates.split(",")]
    rows, knee = sweep(setup, rates, args.seconds, args.seed)
    setup.close()
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "rows": rows, "knee_qps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
