"""One run of one cell: set-up, the measured window, the check, the line.

``chipbench/run.py`` calls :func:`main`; ``chipbench/sweep.py`` and the
tests use the pieces.  The order of a run:

1. refuse to run without a TPU, with fewer chips than the cell asks
   for, or when ``backend="auto"`` does not resolve to the Pallas
   kernels;
2. point JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
3. make the data from ``--seed`` on the device, warm the build's
   programs on one small chunk, then time ``TimeSeriesDB.build`` over
   the whole archive (``build_rows_s``);
4. warm the engine's batch buckets, through ``search_batch`` and
   through ``submit``;
5. drive the window through ``TimeSeriesDB.submit`` on the ``engine``
   searcher (with ``--trace 1`` under the profiler);
6. read the device's peak memory, free the program's state, and decide
   ``correct`` against the plain reference (``chipbench.checks``);
7. print the result as the last line of standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from chipbench import checks, data, reduce_trace, spec, traffic

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
GRACE_S = 60.0          # how long past the close an answer is waited for


class NoChip(Exception):
    """The machine cannot run the cell (no TPU, too few chips, no
    Pallas backend)."""


def log(msg: str) -> None:
    print(msg, flush=True)


def warn(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@contextlib.contextmanager
def compile_clock():
    """Sums, while the block runs, the backend-compile seconds JAX
    reports (``compile_s``, a load from the persistent cache included),
    the compiles (``compiles``) and the persistent-cache hits."""
    import jax
    got = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            got["compile_s"] += secs
            got["compiles"] += 1

    def on_event(event, **_):
        if event == CACHE_HIT_EVENT:
            got["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield got
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def enable_cache(root: Path) -> str:
    """The benchmark's compile cache: a fixed directory in the checkout,
    handed to the program through the variable it reads."""
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def look_for_chip(chips: int):
    """The device, or NoChip."""
    import jax
    from repro.kernels import ops
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    resolved = ops.backend_name(ops.resolve_backend("auto"))
    if resolved != "pallas":
        raise NoChip(f"backend 'auto' resolves to {resolved!r}, not the "
                     "Pallas kernels")
    return devs[0]


def index_spec(config: dict):
    from repro.encoders import IndexSpec
    enc = dict(config["encoder"])
    return IndexSpec(encoder=enc.pop("encoder"), seed=int(enc.pop("seed")),
                     params=enc)


def search_config(config: dict):
    from repro.db import BatchPolicy, SearchConfig
    s = dict(config["search"])
    policy = BatchPolicy(**s.pop("batch_policy"))
    return SearchConfig(batch_policy=policy, **s).validate()


def make_data(dataset: dict, n_queries: int, seed: int):
    """The archive on the device and ``n_queries`` query windows on the
    host, from one seeded stream."""
    import jax
    m = int(dataset["length"])
    stream_dev = jax.device_put(data.make_stream(dataset, n_queries, seed))
    archive = jax.block_until_ready(data.device_windows(
        stream_dev, data.archive_starts(dataset), m))
    queries = np.asarray(data.device_windows(
        stream_dev, data.query_starts(dataset, n_queries), m))
    return archive, queries


class Setup:
    """Everything a window needs, made from one seed."""

    def __init__(self, cell: spec.Cell, seed: int):
        from repro.db import TimeSeriesDB
        cfg = cell.config
        ds = cfg["dataset"]
        self.cell = cell
        self.search_cfg = search_config(cfg)
        self.spec = index_spec(cfg)
        max_batch = self.search_cfg.batch_policy.max_batch
        n_pool = int(cell.traffic["pool"])
        n_warm = max_batch * (max_batch + 5)

        t = time.perf_counter()
        self.archive, queries = make_data(ds, n_pool + n_warm, seed)
        self.pool, self.warm = queries[:n_pool], queries[n_pool:]
        self.data_s = time.perf_counter() - t

        t = time.perf_counter()
        small = TimeSeriesDB.build(self.archive[:int(cfg["warm_rows"])],
                                   spec=self.spec, config=self.search_cfg)
        _block_index(small)
        small.close()
        del small
        self.build_warm_s = time.perf_counter() - t

        t = time.perf_counter()
        self.db = TimeSeriesDB.build(self.archive, spec=self.spec,
                                     config=self.search_cfg)
        _block_index(self.db)
        self.build_s = time.perf_counter() - t
        self.rows = len(self.db)

        t = time.perf_counter()
        warm_engine(self.db, self.warm, self.search_cfg.buckets())
        self.warmup_s = time.perf_counter() - t

    def close(self) -> None:
        """Free the program's state (the archive, made by the benchmark,
        stays for the reference)."""
        if self.db is not None:
            self.db.close()
            self.db = None
        gc.collect()


def _block_index(db) -> None:
    import jax
    ix = db.index
    jax.block_until_ready([a for a in (ix.signatures, ix.keys, ix.series,
                                       ix.env_upper, ix.env_lower)
                           if a is not None])


def warm_engine(db, warm: np.ndarray, buckets) -> None:
    """Compile every shape the window uses: each batch bucket through
    ``search_batch`` (twice, on other queries, so both pair-chunk sizes
    of the re-rank appear), then bursts of every size up to the largest
    bucket through ``submit`` on queries not seen before, so that the
    engine forms, pads and serves each batch size as the window does."""
    import jax.numpy as jnp
    searcher = db.engine.searcher
    n = int(warm.shape[0])
    for rep in range(2):
        for size in buckets:
            rows = (np.arange(size) + rep * size) % n
            searcher.search_batch(jnp.asarray(warm[rows]))
    k = 2 * max(buckets)
    for size in list(range(1, max(buckets) + 1)) * 2:
        futs = [db.submit(warm[(k + i) % n]) for i in range(size)]
        k += size
        for f in futs:
            f.result(timeout=600)


def run_window(setup: Setup, seconds: float, seed: int,
               trace_dir: Optional[Path] = None,
               rate_qps: Optional[float] = None):
    """Drive one window; returns (WindowResult, counters)."""
    import jax
    tr = setup.cell.traffic
    db = setup.db
    hist0 = dict(db.engine.metrics.batch_histogram())
    pool = setup.pool
    if trace_dir is not None:
        inner = db.engine.searcher.search_batch

        def annotated(queries):
            with jax.profiler.TraceAnnotation("engine.search_batch"):
                return inner(queries)
        db.engine.searcher.search_batch = annotated
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    # nothing should compile in the window: where something does, name it
    jax.config.update("jax_log_compiles", True)
    try:
        with jax.profiler.TraceAnnotation(reduce_trace.WINDOW):
            if tr["loop"] == "open":
                times = traffic.schedule(tr, seconds, seed, rate_qps)
                ids = traffic.query_order(len(pool), len(times), seed)
                res = traffic.run_open(db.submit, pool, times, ids, seconds,
                                       GRACE_S)
            else:
                ids = traffic.query_order(len(pool), 1 << 20, seed)
                res = traffic.run_closed(db.submit, pool, int(tr["clients"]),
                                         ids, seconds, GRACE_S)
    finally:
        jax.config.update("jax_log_compiles", False)
        if trace_dir is not None:
            jax.profiler.stop_trace()
            db.engine.searcher.search_batch = inner
    hist1 = db.engine.metrics.batch_histogram()
    sizes = {int(k): int(v) - int(hist0.get(k, 0)) for k, v in hist1.items()
             if int(v) - int(hist0.get(k, 0)) > 0}
    counters = {
        "latency_ms": request_latency_ms(res),
        "requests": len(res.answered),
        "n_candidates": [int(res.results[k].n_candidates)
                         for k in res.answered],
        "batch_sizes": sizes,
        "batches": sum(sizes.values()),
    }
    return res, counters


def request_latency_ms(res) -> list:
    """Each request's latency from its intended send time; one that never
    got an answer counts with the whole wait."""
    return [((res.done_at[k] if res.results[k] is not None
              else res.seconds + GRACE_S) - res.sent_at[k]) * 1e3
            for k in range(res.attempted)]


def end_to_end(setup: Setup, res, setup_s: float) -> dict:
    lat = request_latency_ms(res)
    out = {
        "throughput_qps": res.completed_in_window() / res.seconds,
        "build_rows_s": setup.rows / setup.build_s,
        "setup_s": setup_s,
    }
    if lat:
        out["latency_p95_ms"] = float(np.percentile(lat, 95))
    return out


def check(setup: Setup, res, seed: int) -> dict:
    """Each compared number beside its limit (program state freed)."""
    from chipbench.reference import Reference
    cfg = setup.cell.config
    chk = cfg["check"]
    picks = checks.sample(res.answered, int(chk["sample"]), seed)
    ref = Reference(cfg["encoder"], cfg["search"])
    t = time.perf_counter()
    if picks:
        readings = checks.reference_readings(
            ref, setup.archive, ref.signatures(setup.archive),
            np.stack([setup.pool[res.pool_ids[k]] for k in picks]),
            [np.asarray(res.results[k].ids) for k in picks],
            [np.asarray(res.results[k].dists) for k in picks])
    else:
        readings = {"rank_gap": checks.MISSING, "pair_gap": checks.MISSING,
                    "foreign": 0}
    log(f"check: compared {len(picks)} requests in "
        f"{time.perf_counter() - t:.3f} s")
    return checks.verdict(readings, res.failed, chk["limits"])


def per_layer(cell: spec.Cell, summary: Optional[dict], counters: dict,
              device_kind: str) -> dict:
    ctx = {"trace": summary, "counters": counters, "config": cell.config,
           "peaks": spec.peaks(device_kind, cell.root),
           "work": lambda k: spec.work_module(k, cell.root), "log": log}
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             clock0: float, require_chip: bool = True,
             keep_trace: Optional[Path] = None,
             use_cache: bool = True) -> dict:
    """One whole run; returns the result line as a dict.  Tests pass
    ``require_chip=False`` and ``use_cache=False`` to drive it on the
    CPU."""
    import jax
    if require_chip:
        dev = look_for_chip(cell.chips)
    else:
        dev = jax.devices()[0]
    if use_cache:
        enable_cache(cell.root)
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
        f"jax {jax.__version__}")

    with compile_clock() as clk:
        setup = Setup(cell, seed)
    log(f"setup: data_s={setup.data_s:.6f} build_warm_s="
        f"{setup.build_warm_s:.6f} build_s={setup.build_s:.6f} "
        f"warmup_s={setup.warmup_s:.6f} compile_s={clk['compile_s']:.6f} "
        f"compiles={clk['compiles']} cache_hits={clk['cache_hits']} "
        f"rows={setup.rows} index_bytes={setup.db.index.nbytes()}")

    trace_dir = None
    if trace:
        trace_dir = Path(keep_trace) if keep_trace else Path(
            tempfile.mkdtemp(prefix="chipbench-trace-"))
    setup_s = time.perf_counter() - clock0
    with compile_clock() as wclk:
        res, counters = run_window(setup, seconds, seed, trace_dir)
    lag = np.asarray(res.send_lag_s) * 1e3
    log(f"window: attempted={res.attempted} answered={len(res.answered)} "
        f"in_window={res.completed_in_window()} "
        f"compiles_in_window={wclk['compiles']} "
        f"send_lag_ms_p50={np.percentile(lag, 50):.6f} "
        f"send_lag_ms_max={lag.max(initial=0.0):.6f} "
        f"batches={counters['batches']} batch_sizes={counters['batch_sizes']}")
    for k, e in enumerate(res.errors):
        if e:
            warn(f"request {k} failed: {e}")
            break

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    result = {"correct": False, "attempted": res.attempted,
              "failed": res.failed}
    if trace:
        summary = reduce_trace.summarize(reduce_trace.find_xplane(trace_dir))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["metrics"] = per_layer(cell, summary, counters,
                                      dev.device_kind)
        result["breakdown"] = reduce_trace.breakdown(summary)
    else:
        e2e = end_to_end(setup, res, setup_s)
        result["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in e2e}
    result["device"] = device

    setup.close()
    verdict = check(setup, res, seed)
    result["correct"] = checks.passed(verdict)
    result["checks"] = verdict
    for name, c in verdict.items():
        warn(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None, clock0: Optional[float] = None) -> int:
    clock0 = time.perf_counter() if clock0 is None else clock0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="write the profiler trace here and keep it")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          clock0, keep_trace=args.keep_trace)
    except NoChip as e:
        warn(f"chipbench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
