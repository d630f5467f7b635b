"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only table3] [--scale smoke]
      [--json] [--out DIR] [--baseline [DIR]] [--threshold F]
      [--min-lb-pruned F] [--min-encode-speedup F]

Prints ``name,us_per_call,derived`` CSV lines (benchmarks/common.emit).
With ``--json``, additionally writes one schema-validated
``BENCH_<module>.json`` trajectory file per module (repro.bench), each
carrying the per-stage encode/probe/lb/dtw hot-path breakdown; with
``--baseline`` the run is diffed against a committed baseline directory
and exits nonzero on perf regressions beyond the noise threshold
(the CI ``bench-smoke`` gate — DESIGN.md §8).
"""
import argparse
import os
import sys
import time

MODULES = [
    ("table1_lb_pruning", "Table 1: LB pruning collapse vs length"),
    ("table2_precision", "Table 2 + Fig 6: SSH vs SRP precision/NDCG"),
    ("table3_query_time", "Table 3: query time SSH vs UCR vs brute"),
    ("table4_pruning", "Table 4: candidates pruned"),
    ("fig7_param_study", "Figs 7-12: W / delta / n parameter studies"),
    ("kernel_bench", "kernel micro-benchmarks"),
    ("serving_bench", "serving throughput: batched engine vs sequential"),
    ("ingest_bench", "streaming ingest: sketch throughput, shard merge, "
                     "memory"),
    ("subseq_bench", "subsequence search: rolling vs naive encode, query "
                     "latency vs stream length"),
    ("dist_bench", "resilient fleet: p99 under dead+slow workers, "
                   "bit-identical recovery, zero-loss drain"),
    ("loadgen_bench", "closed-loop load sweep: max sustainable qps, "
                      "adaptive-vs-fixed batching at the knee"),
]

#: Committed smoke-scale baseline (regenerate with
#: ``--json --scale smoke --out benchmarks/baselines/smoke``).
DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baselines",
                                "smoke")


def _parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="benchmarks.run",
        description="SSH-repro benchmark harness (see module docstring)")
    ap.add_argument("--only", type=str, default=None,
                    help="run only modules whose name contains this "
                         "substring (errors if nothing matches)")
    ap.add_argument("--scale", choices=("smoke", "small", "full"),
                    default=None,
                    help="workload scale (overrides $BENCH_SCALE)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<module>.json trajectory files")
    ap.add_argument("--out", type=str, default=".",
                    help="directory for BENCH_*.json output (default: cwd)")
    ap.add_argument("--baseline", nargs="?", const=DEFAULT_BASELINE,
                    default=None, metavar="DIR",
                    help="diff this run against a baseline report dir "
                         f"(default when bare: {DEFAULT_BASELINE}); "
                         "exits nonzero on regression — implies --json")
    ap.add_argument("--threshold", type=float, default=None,
                    help="relative slowdown allowed before an entry is a "
                         "regression (1.0 = 2x baseline; default from "
                         "repro.bench.regression)")
    ap.add_argument("--min-us", type=float, default=None,
                    help="ignore timing entries under this many µs "
                         "(noise floor)")
    ap.add_argument("--min-lb-pruned", type=float, default=None,
                    metavar="F",
                    help="fail unless every table3/ecg case pruned at "
                         "least this fraction of hash candidates before "
                         "full DTW (cascade + LB_Improved effectiveness "
                         "gate; implies --json)")
    ap.add_argument("--min-encode-speedup", type=float, default=None,
                    metavar="F",
                    help="fail unless the subseq rolling encode beat the "
                         "naive per-window encode by at least this factor "
                         "(DESIGN.md §10 tentpole gate; implies --json)")
    ap.add_argument("--max-p99-degradation", type=float, default=None,
                    metavar="F",
                    help="fail unless dist_bench's p99 with one dead and "
                         "one slow worker stayed within this factor of "
                         "the healthy p99, recovery was bit-identical, "
                         "and the engine drain lost zero queries "
                         "(DESIGN.md §11 tentpole gate; implies --json)")
    ap.add_argument("--min-sustainable-qps", type=float, default=None,
                    metavar="F",
                    help="fail unless loadgen_bench's offered-load sweep "
                         "sustained at least this many qps under its p99 "
                         "SLO, and the adaptive policy's answers at the "
                         "knee were bit-identical to fixed batching "
                         "(DESIGN.md §12 tentpole gate; implies --json)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse_args(argv)
    if args.scale:
        # must land before benchmarks.common is imported (it reads the
        # env at import time to size the datasets)
        if "benchmarks.common" in sys.modules \
                and sys.modules["benchmarks.common"].SCALE != args.scale:
            print("error: --scale given after benchmarks.common was "
                  "imported at a different scale", file=sys.stderr)
            return 2
        os.environ["BENCH_SCALE"] = args.scale
    if args.baseline is not None or args.min_lb_pruned is not None \
            or args.min_encode_speedup is not None \
            or args.max_p99_degradation is not None \
            or args.min_sustainable_qps is not None:
        args.json = True

    modules = MODULES
    if args.only:
        modules = [(m, d) for m, d in MODULES if args.only in m]
        if not modules:
            names = ", ".join(m for m, _ in MODULES)
            print(f"error: --only {args.only!r} matches no benchmark "
                  f"module; valid module names: {names}", file=sys.stderr)
            return 2

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.bench import BenchRunner
    from benchmarks import common
    runner = BenchRunner(scale=common.SCALE, out_dir=args.out,
                         write_json=args.json)
    common.set_runner(runner)

    t0 = time.time()
    for mod_name, desc in modules:
        print(f"# === {mod_name}: {desc} ===", flush=True)
        runner.start_module(mod_name)
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        t = time.time()
        mod.run()
        path = runner.finish_module()
        if path is not None:
            print(f"# wrote {path}", flush=True)
        print(f"# {mod_name} done in {time.time()-t:.1f}s", flush=True)
    print(f"# all benchmarks done in {time.time()-t0:.1f}s")

    rc = 0
    if args.baseline is not None:
        rc = _gate(args, [m for m, _ in modules])
    if args.min_lb_pruned is not None:
        rc = max(rc, _lb_gate(args))
    if args.min_encode_speedup is not None:
        rc = max(rc, _encode_gate(args))
    if args.max_p99_degradation is not None:
        rc = max(rc, _p99_gate(args))
    if args.min_sustainable_qps is not None:
        rc = max(rc, _sustainable_gate(args))
    return rc


def _gate(args, module_names) -> int:
    """Baseline diff over the modules that just ran; nonzero on failure."""
    from repro.bench import regression as reg
    from repro.bench import compare_dirs

    kw = {}
    if args.threshold is not None:
        kw["rel_threshold"] = args.threshold
    if args.min_us is not None:
        kw["min_us"] = args.min_us
    findings, missing = compare_dirs(args.out, args.baseline,
                                     modules=module_names, **kw)

    for f in findings:
        print(f"# baseline: {f}")
    for name in missing:
        print(f"# baseline: MISSING REPORT {name} (in baseline, not "
              "emitted by this run)")
    n_fail = len(reg.failures(findings)) + len(missing)
    if n_fail:
        print(f"# baseline: FAIL ({n_fail} regression(s)/missing "
              f"entr(ies) vs {args.baseline})")
        return 1
    print(f"# baseline: OK (no regressions vs {args.baseline})")
    return 0


def _lb_gate(args) -> int:
    """Pruning-effectiveness floor over the table3 ECG cases: the LB
    cascade + LB_Improved must spare at least ``--min-lb-pruned`` of the
    hash candidates from full DTW.  A drop below the floor means a bound
    or the seed threshold silently weakened (results would still be
    correct — the bounds are sound — but the latency claim would not
    hold)."""
    from repro.bench import load_report
    path = os.path.join(args.out, "BENCH_table3_query_time.json")
    if not os.path.exists(path):
        print("# lb-gate: SKIP (table3_query_time not in this run)")
        return 0
    checked, bad = 0, []
    for r in load_report(path).results:
        if not r.name.startswith("table3/ecg/"):
            continue
        checked += 1
        frac = r.lb_pruned_frac
        if frac is None or frac < args.min_lb_pruned:
            bad.append((r.name, frac))
        else:
            print(f"# lb-gate: {r.name} lb_pruned_frac={frac:.3f} "
                  f">= {args.min_lb_pruned}")
    for name, frac in bad:
        print(f"# lb-gate: FAIL {name} lb_pruned_frac={frac} < "
              f"{args.min_lb_pruned}")
    if bad or not checked:
        if not checked:
            print("# lb-gate: FAIL (no table3/ecg entries in report)")
        return 1
    print("# lb-gate: OK")
    return 0


def _encode_gate(args) -> int:
    """Rolling-encode advantage floor: the subseq build must amortise the
    sketch grid across overlapping windows — ``speedup`` (naive
    per-window µs / rolling per-window µs) dropping below the floor means
    the rolling path silently degraded to per-window work (e.g. the
    sparse CWS or shared-grid gather fell back to the dense pipeline)."""
    from repro.bench import load_report
    path = os.path.join(args.out, "BENCH_subseq_bench.json")
    if not os.path.exists(path):
        print("# encode-gate: SKIP (subseq_bench not in this run)")
        return 0
    checked, bad = 0, []
    for r in load_report(path).results:
        if not r.name.endswith("/encode"):
            continue
        checked += 1
        speedup = r.derived.get("speedup") if r.derived else None
        if speedup is None or float(speedup) < args.min_encode_speedup:
            bad.append((r.name, speedup))
        else:
            print(f"# encode-gate: {r.name} speedup={float(speedup):.1f}x "
                  f">= {args.min_encode_speedup}")
    for name, speedup in bad:
        print(f"# encode-gate: FAIL {name} speedup={speedup} < "
              f"{args.min_encode_speedup}")
    if bad or not checked:
        if not checked:
            print("# encode-gate: FAIL (no /encode entries in report)")
        return 1
    print("# encode-gate: OK")
    return 0


def _p99_gate(args) -> int:
    """Resilience-under-failure gate over the dist_bench scenarios: the
    p99 with one dead + one 10x-slow worker must stay within
    ``--max-p99-degradation`` of the healthy p99 (hedging/failover are
    doing their job), the recovered top-k must have been bit-identical,
    and the live engine drain must have lost zero queries."""
    from repro.bench import load_report
    path = os.path.join(args.out, "BENCH_dist_bench.json")
    if not os.path.exists(path):
        print("# p99-gate: SKIP (dist_bench not in this run)")
        return 0
    checked, bad = 0, []
    for r in load_report(path).results:
        d = r.derived or {}
        if r.name.endswith("/faulty"):
            checked += 1
            ratio = d.get("p99_ratio")
            if ratio is None or float(ratio) > args.max_p99_degradation:
                bad.append((r.name, f"p99_ratio={ratio} > "
                            f"{args.max_p99_degradation}"))
            elif not d.get("recovered_identical"):
                bad.append((r.name, "recovered_identical is false"))
            else:
                print(f"# p99-gate: {r.name} p99_ratio={float(ratio):.2f} "
                      f"<= {args.max_p99_degradation}, recovery "
                      "bit-identical")
        elif r.name.endswith("/drain"):
            checked += 1
            lost = d.get("lost_queries")
            if lost is None or int(lost) != 0:
                bad.append((r.name, f"lost_queries={lost} != 0"))
            else:
                print(f"# p99-gate: {r.name} lost_queries=0 over "
                      f"{d.get('n_requests')} requests")
    for name, why in bad:
        print(f"# p99-gate: FAIL {name} {why}")
    if bad or not checked:
        if not checked:
            print("# p99-gate: FAIL (no /faulty or /drain entries "
                  "in report)")
        return 1
    print("# p99-gate: OK")
    return 0


def _sustainable_gate(args) -> int:
    """Serving-capacity floor + answer-invariance over loadgen_bench:
    the offered-load sweep must have sustained ``--min-sustainable-qps``
    under its p99 SLO, and the adaptive policy at the knee must have
    returned bit-identical top-k to fixed batching (the adaptive control
    law is a scheduling change only — any answer drift is a bug, not a
    tuning issue)."""
    from repro.bench import load_report
    path = os.path.join(args.out, "BENCH_loadgen_bench.json")
    if not os.path.exists(path):
        print("# sustainable-gate: SKIP (loadgen_bench not in this run)")
        return 0
    checked, bad = 0, []
    for r in load_report(path).results:
        d = r.derived or {}
        if r.name.endswith("/max_sustainable"):
            checked += 1
            qps = d.get("max_sustainable_qps")
            if qps is None or float(qps) < args.min_sustainable_qps:
                bad.append((r.name, f"max_sustainable_qps={qps} < "
                            f"{args.min_sustainable_qps}"))
            else:
                print(f"# sustainable-gate: {r.name} "
                      f"max_sustainable_qps={float(qps):.1f} >= "
                      f"{args.min_sustainable_qps} (SLO p99 <= "
                      f"{d.get('slo_p99_ms')}ms)")
        elif r.name.endswith("/knee/adaptive"):
            checked += 1
            if not d.get("identical"):
                bad.append((r.name, "identical is false (adaptive "
                            "changed answers vs fixed)"))
            else:
                print(f"# sustainable-gate: {r.name} bit-identical to "
                      f"fixed, p99_ratio_vs_best_fixed="
                      f"{d.get('p99_ratio_vs_best_fixed')}")
    for name, why in bad:
        print(f"# sustainable-gate: FAIL {name} {why}")
    if bad or checked < 2:
        if checked < 2:
            print("# sustainable-gate: FAIL (missing /max_sustainable "
                  "or /knee/adaptive entries in report)")
        return 1
    print("# sustainable-gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
