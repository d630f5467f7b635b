#!/usr/bin/env python3
"""Drive the SSH query path once on a TPU and check its answers.

    python3 chip_smoke.py              # one chip: archive, query, long, ingest
    python3 chip_smoke.py --chips 4    # four chips: distributed vs batched

Everything goes through the library's normal entry points
(``TimeSeriesDB.build`` with the ``ssh-ecg`` arch's full published
encoder widths, the ``engine`` searcher with ``backend="auto"``,
``submit`` / ``search_batch`` / ``add_stream``), in this one process.
Data is synthetic ECG made from ``--seed``.

One chip, in order:

* archive — builds 2^20 z-normalised length-128 windows into a
  device-resident index and prints its bytes;
* query   — warms every batch bucket, serves 32 requests through the
  engine (each must find itself first), compares 16 of them with
  ``backend="jnp"`` on the chip (identical top-k ids) and reports
  precision@10 against brute-force DTW (the plain jnp reference);
* long    — the same path at length 2048 and the paper's 5% band;
* ingest  — an ``"ssh-cs"`` database grown by ``add_stream``/``flush``,
  queried for a row that was streamed in.

``--chips 4`` runs only the ``distributed`` searcher (a ``shard_map``
over every local device) and requires its ids to equal the ``batched``
searcher's on one chip for the same index and ``SearchConfig``.

Each phase sets ``top_c`` to hold the largest group of rows that share
one whole signature (see ``fit_top_c``).

The script exits non-zero, printing no result, when JAX finds no TPU,
when ``backend="auto"`` does not resolve to the Pallas kernels, or when
any check fails.  On success its last line is one JSON object naming
the device.  Timings printed on earlier lines are information only.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import get_arch                      # noqa: E402
from repro.core.search import brute_force_topk, precision_at_k  # noqa: E402
from repro.data.timeseries import extract_subsequences, synthetic_ecg  # noqa: E402,E501
from repro.db import BatchPolicy, TimeSeriesDB          # noqa: E402
from repro.encoders import IndexSpec                    # noqa: E402

ARCH = get_arch("ssh-ecg")
ARCHIVE_ROWS = 1 << 20
PHASE_ROWS = 65536          # long, ingest and distributed phases
MAX_BATCH = 8
RESULT_TIMEOUT_S = 600.0
# JAX's own compile-time events (jax.monitoring): the XLA backend compile
# of one program, a load from the persistent cache included, and a hit
# in that cache.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    """A check of the smoke run failed; the message says which."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def ecg_windows(rows: int, length: int, seed: int) -> np.ndarray:
    """``rows`` z-normalised stride-1 windows of one synthetic ECG."""
    stream = synthetic_ecg(rows + length - 1, seed=seed)
    return extract_subsequences(stream, length, stride=1, znorm=True)


def build(series: np.ndarray, spec, config):
    """(db, device series, build seconds) — the timed part excludes the
    host-to-device copy of the raw series (data loading is set-up)."""
    import jax
    import jax.numpy as jnp
    dev_series = jax.block_until_ready(jnp.asarray(series))
    t0 = time.perf_counter()
    db = TimeSeriesDB.build(dev_series, spec=spec, config=config)
    ix = db.index
    jax.block_until_ready([a for a in (ix.signatures, ix.keys,
                                       ix.env_upper, ix.env_lower)
                           if a is not None])
    return db, dev_series, time.perf_counter() - t0


@contextlib.contextmanager
def compile_clock():
    """Yields a dict that sums, while the block runs, the seconds JAX
    reports for backend compiles (``compile_s``) and the persistent-cache
    hits (``cache_hits``)."""
    import jax
    got = {"compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            got["compile_s"] += secs

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


def warm_buckets(db, queries) -> str:
    """Run one batch of every padded size the engine may form; returns
    the wall seconds (compile and first execution) and, apart, the
    backend compile seconds JAX reported in them."""
    n = int(queries.shape[0])
    with compile_clock() as clock:
        t0 = time.perf_counter()
        for size in db.config.buckets():
            db.engine.searcher.search_batch(queries[np.arange(size) % n])
        wall = time.perf_counter() - t0
    return (f"warmup_seconds={wall:.3f} "
            f"warmup_compile_seconds={clock['compile_s']:.3f} "
            f"compile_cache_hits={clock['cache_hits']}")


def fit_top_c(db, what: str) -> None:
    """Raise ``db``'s top_c to hold the largest group of rows that share
    one whole signature.

    Rows with the query's signature tie with it at the top collision
    count, and the probe keeps the lowest ids of a tie, so a top_c below
    the query's group size can cut the query itself.  At length 128 the
    paper's ECG encoder (W=80, δ=3, n=15) sees 17 sketch bits (3
    shingles), and over 2^20 stride-1 windows most rows share theirs with
    thousands of others: the arch default top_c fails there.
    """
    sig = np.ascontiguousarray(np.asarray(db.index.signatures))
    rows = sig.view(np.dtype((np.void, sig.itemsize * sig.shape[1])))
    sizes = np.unique(rows.ravel(), return_counts=True)[1]
    largest = int(sizes.max())
    default = db.config.top_c
    top_c = max(default, 1 << (largest - 1).bit_length())
    per_row = np.repeat(sizes, sizes)
    log(f"{what}: signature tie groups={len(sizes)} "
        f"median_group_of_a_row={int(np.median(per_row))} "
        f"largest={largest} rows_in_groups_over_default="
        f"{float(np.mean(per_row > default)):.4f} top_c={top_c} "
        f"(default {default})")
    db.reconfigure(top_c=top_c)


def serve(db, queries):
    """Submit every query row through the engine at once; returns
    (per-request results, per-request latency seconds)."""
    stamps = {}
    futs = []
    for i in range(int(queries.shape[0])):
        t0 = time.perf_counter()
        fut = db.submit(queries[i])
        fut.add_done_callback(
            lambda f, i=i: stamps.__setitem__(i, time.perf_counter()))
        futs.append((i, t0, fut))
    results, lat = [], []
    for i, t0, fut in futs:
        results.append(fut.result(timeout=RESULT_TIMEOUT_S))
    for i, t0, _ in futs:
        lat.append(stamps[i] - t0)
    return results, np.asarray(lat)


def require_self_match(results, qids, what: str) -> None:
    bad = [(int(q), int(r.ids[0]) if len(r.ids) else None)
           for q, r in zip(qids, results)
           if not len(r.ids) or int(r.ids[0]) != int(q)]
    check(not bad, f"{what}: top-1 is not the query itself for "
                   f"{len(bad)}/{len(qids)} requests (query, top1): "
                   f"{bad[:8]}")


def phase_archive(rows: int, seed: int, *, backend="auto"):
    """Build the length-128 ECG archive behind the engine searcher."""
    import jax
    spec = ARCH.index_spec()
    cfg = ARCH.search_config(
        length=128, searcher="engine", backend=backend,
        batch_policy=BatchPolicy(max_batch=MAX_BATCH, max_wait_ms=2.0))
    series = ecg_windows(rows, 128, seed)
    db, dev_series, build_s = build(series, spec, cfg)
    fit_top_c(db, "archive")
    nbytes = db.index.nbytes()
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    share = f" ({nbytes / limit:.1%} of {limit} B)" if limit else ""
    log(f"archive: rows={len(db)} length=128 spec={spec.to_dict()} "
        f"top_c={db.config.top_c} band={cfg.band}")
    log(f"archive: index_device_bytes={nbytes}{share} "
        f"build_seconds={build_s:.3f}")
    return db, dev_series


def phase_query(db, dev_series, seed: int, *, n_requests: int = 32,
                n_compare: int = 16):
    """Serve ``n_requests`` archive rows through the engine; each must
    find itself.  ``n_compare`` of them also run on ``backend="jnp"``
    (identical top-k ids required) and against brute-force DTW."""
    import jax
    rng = np.random.default_rng(seed + 1)
    qids = rng.choice(len(db), size=n_requests, replace=False)
    queries = dev_series[qids]
    warm = warm_buckets(db, queries)
    with compile_clock() as clock:
        results, lat = serve(db, queries)
    require_self_match(results, qids, "query")

    cfg = db.config
    sub = qids[:n_compare]
    t0 = time.perf_counter()
    with db.with_config(cfg.replace(searcher="batched",
                                    backend="jnp")) as ref_db:
        ref = ref_db.search_batch(dev_series[sub])
    jnp_s = time.perf_counter() - t0
    worst = 0.0
    for q, got, want in zip(sub, results, ref):
        check(np.array_equal(got.ids, want.ids),
              f"query {int(q)}: pallas ids {got.ids.tolist()} != "
              f"jnp ids {want.ids.tolist()}")
        g = np.asarray(got.dists, np.float64)
        w = np.asarray(want.dists, np.float64)
        rel = np.abs(g - w) / np.maximum(np.abs(w), 1e-30)
        rel[(g == 0) & (w == 0)] = 0.0
        worst = max(worst, float(rel.max(initial=0.0)))
    prec, brute_s = [], []
    for q, got in zip(sub, results):
        t0 = time.perf_counter()
        gold, _ = brute_force_topk(dev_series[int(q)], dev_series,
                                   cfg.topk, band=cfg.band)
        brute_s.append(time.perf_counter() - t0)
        prec.append(precision_at_k(got.ids, gold, cfg.topk))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"query: requests={n_requests} self_match={n_requests}/{n_requests}"
        f" pallas_vs_jnp_ids_equal={len(sub)}/{len(sub)} "
        f"max_rel_dist_diff={worst:.3e} jnp_seconds={jnp_s:.3f}")
    log(f"query: precision@{cfg.topk}={np.mean(prec):.4f} over {len(sub)} "
        f"queries vs brute_force_topk (min {min(prec):.2f}) "
        f"brute_force_seconds={sum(brute_s):.3f} "
        f"(first {brute_s[0]:.3f})")
    log(f"query: p50_ms={np.percentile(lat, 50) * 1e3:.3f} "
        f"p99_ms={np.percentile(lat, 99) * 1e3:.3f} "
        f"serve_compile_seconds={clock['compile_s']:.3f} {warm} "
        f"peak_bytes_in_use={peak}")
    return {"precision": float(np.mean(prec)), "max_rel_diff": worst}


def phase_long(rows: int, seed: int, *, backend="auto",
               length: int = 2048, n_requests: int = 8):
    """The engine path at a long query length and the 5% band."""
    cfg = ARCH.search_config(
        length=length, searcher="engine", backend=backend,
        batch_policy=BatchPolicy(max_batch=MAX_BATCH, max_wait_ms=2.0))
    series = ecg_windows(rows, length, seed + 2)
    db, dev_series, build_s = build(series, ARCH.index_spec(), cfg)
    fit_top_c(db, "long")
    with db:
        qids = np.random.default_rng(seed + 3).choice(
            len(db), size=n_requests, replace=False)
        queries = dev_series[qids]
        warm = warm_buckets(db, queries)
        results, lat = serve(db, queries)
        require_self_match(results, qids, f"long (m={length})")
    log(f"long: rows={rows} length={length} band={cfg.band} "
        f"top_c={db.config.top_c} build_seconds={build_s:.3f} {warm} "
        f"self_match={n_requests}/{n_requests} "
        f"p50_ms={np.percentile(lat, 50) * 1e3:.3f}")


def phase_ingest(rows: int, seed: int, *, backend="auto", length=2048,
                 blocks: int = 4, block_rows: int = 256):
    """``"ssh-cs"`` database grown by streamed blocks, then queried for
    rows that were streamed in (the count-sketch kernel's path)."""
    spec = IndexSpec(encoder="ssh-cs")
    cfg = ARCH.search_config(
        length=length, searcher="engine", backend=backend,
        batch_policy=BatchPolicy(max_batch=MAX_BATCH, max_wait_ms=2.0))
    base = ecg_windows(rows, length, seed + 4)
    extra = ecg_windows(blocks * block_rows, length, seed + 5)
    db, _, build_s = build(base, spec, cfg)
    fit_top_c(db, "ingest")
    with db:
        t0 = time.perf_counter()
        for b in range(blocks):
            db.add_stream(extra[b * block_rows:(b + 1) * block_rows])
        db.flush()
        ingest_s = time.perf_counter() - t0
        check(len(db) == rows + blocks * block_rows,
              f"ingest: {len(db)} rows after flush, expected "
              f"{rows + blocks * block_rows}")
        picks = np.random.default_rng(seed + 6).choice(
            blocks * block_rows, size=4, replace=False)
        results = db.search_batch(extra[picks])
        require_self_match(results, rows + picks, "ingest")
    log(f"ingest: encoder=ssh-cs base_rows={rows} length={length} "
        f"streamed={blocks}x{block_rows} build_seconds={build_s:.3f} "
        f"stream_fold_seconds={ingest_s:.3f} self_match=4/4")


def phase_distributed(rows: int, seed: int, *, backend="auto",
                      n_queries: int = 16):
    """``distributed`` (shard_map over every local device) vs ``batched``
    (one device) on the same index and config: ids must be equal.

    ``top_c`` is the row count, so both searchers re-rank every row that
    collides with the query in at least one hash and the comparison tests
    the sharded path itself.  With a smaller top_c the shard-local cut
    (top_c / shards per shard) keeps other candidates than the global
    one by design.
    """
    import jax
    cfg = ARCH.search_config(length=128, searcher="batched",
                             backend=backend, top_c=rows,
                             multiprobe_offsets=1)
    series = ecg_windows(rows, 128, seed + 7)
    db, dev_series, build_s = build(series, ARCH.index_spec(), cfg)
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    qids = np.random.default_rng(seed + 8).choice(rows, size=n_queries,
                                                  replace=False)
    queries = dev_series[qids]
    with db:
        want = db.search_batch(queries)
    dist = TimeSeriesDB(db.index, cfg.replace(searcher="distributed"),
                        mesh=mesh)
    with dist:
        t0 = time.perf_counter()
        got = dist.search_batch(queries)
        dist_s = time.perf_counter() - t0
    for q, g, w in zip(qids, got, want):
        check(np.array_equal(g.ids, w.ids),
              f"distributed ids {g.ids.tolist()} != batched ids "
              f"{w.ids.tolist()} for query {int(q)}")
    require_self_match(got, qids, "distributed")
    log(f"distributed: devices={jax.device_count()} rows={rows} length=128 "
        f"top_c={cfg.top_c} band={cfg.band} build_seconds={build_s:.3f} "
        f"first_batch_seconds={dist_s:.3f} "
        f"ids_equal_to_batched={n_queries}/{n_queries}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed-vs-batched phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.kernels import ops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: FAIL — JAX found no TPU (platform "
                 f"{dev.platform!r}, device {dev.device_kind!r})")
    resolved = ops.backend_name(ops.resolve_backend("auto"))
    if resolved != "pallas":
        sys.exit(f"chip_smoke: FAIL — backend 'auto' resolves to "
                 f"{resolved!r}, not the Pallas kernels")
    count = len(jax.devices())
    log(f"device: {dev.platform} {dev.device_kind} x{count} "
        f"jax {jax.__version__}")
    if count != args.chips:
        sys.exit(f"chip_smoke: FAIL — --chips {args.chips} but JAX sees "
                 f"{count} devices")

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_distributed(PHASE_ROWS, args.seed)
        else:
            db, dev_series = phase_archive(ARCHIVE_ROWS, args.seed)
            log(f"elapsed_seconds={time.perf_counter() - t0:.3f}")
            with db:
                phase_query(db, dev_series, args.seed)
            del db, dev_series
            log(f"elapsed_seconds={time.perf_counter() - t0:.3f}")
            phase_long(PHASE_ROWS, args.seed)
            log(f"elapsed_seconds={time.perf_counter() - t0:.3f}")
            phase_ingest(PHASE_ROWS, args.seed)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAIL — {e}")
    log(f"total_seconds={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
