#!/usr/bin/env python3
"""The readings a cell's check limits are set from, at the cell's own
size: the control's (the upper end) and the program's (the lower end).

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 \
        [--program-seeds 21,22,...,32 --seconds 10]

For each seed: the run's data, and the requests a run would sample (the
same number, drawn from the pool by the seed); then the control, the
plain reference computed in bfloat16 (one step below the
configuration's float32), put in the program's place and compared with
the float32 reference as a run compares the program.  For each of
``--program-seeds``: a run's set-up and a window of ``--seconds`` at the
cell's own load, checked as a run checks it; the set-up's programs are
compiled once for all of them.  The benchmark's runs never run this.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from chipbench import checks, harness, spec  # noqa: E402


def readings(cell, seed: int) -> dict:
    import jax.numpy as jnp
    from chipbench.reference import Reference
    cfg = cell.config
    archive, pool = harness.make_data(cfg["dataset"],
                                      int(cell.traffic["pool"]), seed)
    picks = checks.sample(list(range(len(pool))),
                          int(cfg["check"]["sample"]), seed)
    queries = pool[picks]
    ref = Reference(cfg["encoder"], cfg["search"])
    ctl = Reference(cfg["encoder"], cfg["search"], dtype=jnp.bfloat16)
    ids, dists = ctl.search(archive, ctl.signatures(archive), queries)
    control = checks.reference_readings(
        ref, archive, ref.signatures(archive), queries, list(ids),
        list(dists))
    del archive
    gc.collect()
    return {"seed": seed, "requests": len(picks), "control": control}


def program_readings(cell, seed: int, seconds: float) -> dict:
    """A run's readings on ``seed`` without its result line."""
    setup = harness.Setup(cell, seed)
    res, _ = harness.run_window(setup, seconds, seed)
    setup.close()
    verdict = harness.check(setup, res, seed)
    del setup
    gc.collect()
    return {"seed": seed, "attempted": res.attempted,
            "program": {k: v["value"] for k, v in verdict.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Control readings of one "
                                 "cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        harness.look_for_chip(cell.chips)
    except harness.NoChip as e:
        harness.warn(f"control: {e}")
        return 2
    harness.enable_cache(cell.root)
    rows = []
    for s in filter(None, args.program_seeds.split(",")):
        row = program_readings(cell, int(s), args.seconds)
        harness.log("program: " + json.dumps(row))
        rows.append(row)
    for s in filter(None, args.seeds.split(",")):
        row = readings(cell, int(s))
        harness.log("control: " + json.dumps(row))
        rows.append(row)
    print(json.dumps({"workload": args.workload, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
