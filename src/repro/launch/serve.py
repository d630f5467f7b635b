"""Serving launcher — SSH query serving (paper Alg. 2) or LM decode.

SSH arches serve through the ``repro.db`` facade: one ``TimeSeriesDB``
whose ``SearchConfig`` (read from the arch registry — no hand-plumbed
knob tuples) routes to the dynamic-batching engine by default, the
sequential re-rank with ``--sequential``, or a saved database with
``--db-dir`` (skipping the O(N) rebuild the paper's retraining-free
hashing makes avoidable).

    PYTHONPATH=src python -m repro.launch.serve --arch ssh-ecg --requests 32
    PYTHONPATH=src python -m repro.launch.serve --arch ssh-ecg --no-smoke
    PYTHONPATH=src python -m repro.launch.serve --arch ssh-ecg --sequential
    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --smoke
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache

SERVE_LENGTH = 128


def _ssh_db(arch, config, db_dir=None, smoke: bool = True):
    """(queries pool, TimeSeriesDB) — loaded from ``db_dir`` when it holds
    a saved database, else built from a synthetic ECG stream with the
    arch's smoke encoder (``smoke``) or its full published widths.

    A loaded database keeps its *saved* search knobs (topk/top_c/band
    were chosen for its series length); only the serving-policy fields
    of ``config`` (searcher, backend, batcher) are overlaid.  The query
    pool is generated at the database's length either way.
    """
    from repro.data.timeseries import extract_subsequences, synthetic_ecg
    from repro.db import TimeSeriesDB, is_database_dir
    if db_dir:
        if not is_database_dir(db_dir):
            raise FileNotFoundError(
                f"--db-dir {db_dir}: no saved TimeSeriesDB there "
                "(build one with repro.launch.build_index)")
        tsdb = TimeSeriesDB.load(db_dir)
        overlay = dict(
            searcher=config.searcher, backend=config.backend,
            batch_policy=config.batch_policy,
            replication=config.replication,
            fleet_workers=config.fleet_workers,
            hedge_policy=config.hedge_policy, hedge_ms=config.hedge_ms)
        if config.replication > 1:
            overlay["multiprobe_offsets"] = 1    # fleet is single-probe
        tsdb = tsdb.with_config(tsdb.config.replace(**overlay))
        length = tsdb.length
        print(f"loaded database ({len(tsdb)} series of length {length}) "
              f"from {db_dir}")
    else:
        length = SERVE_LENGTH
        tsdb = None
    stream = synthetic_ecg(8000, seed=5)
    series = jnp.asarray(extract_subsequences(stream, length,
                                              stride=1, znorm=True))
    if tsdb is None:
        tsdb = TimeSeriesDB.build(series, spec=arch.index_spec(smoke=smoke),
                                  config=config)
    return series, tsdb


def serve_ssh(arch, requests: int, batch_size: int, wait_ms: float,
              backend: str = "auto", db_dir=None, replication: int = 1,
              fleet_workers=None, hedge_ms: float = 30.0,
              batch_mode: str = "fixed", smoke: bool = True):
    """Engine-based serving: dynamic batching + batched probe/re-rank.

    ``batch_mode="adaptive"`` lets the batcher set its own wait from the
    queue depth and the service-time EWMA (DESIGN.md §12); answers are
    bit-identical to fixed batching either way.  ``replication >= 2``
    serves through the resilient fleet tier (replicated shards, hedged
    fan-out, failover — DESIGN.md §11) behind the same engine."""
    from repro.db import BatchPolicy
    policy = BatchPolicy(mode=batch_mode, max_batch=batch_size,
                         max_wait_ms=wait_ms)
    cfg = arch.search_config(length=SERVE_LENGTH, searcher="engine",
                             backend=backend, batch_policy=policy,
                             replication=replication,
                             fleet_workers=fleet_workers,
                             hedge_ms=hedge_ms)
    if replication > 1 and cfg.multiprobe_offsets > 1:
        # the fleet shard probe matches the shard_map fan-out, which is
        # single-probe; drop the arch's multiprobe rather than refuse
        print(f"replication={replication}: fleet serving is single-probe "
              f"(overriding arch multiprobe_offsets="
              f"{cfg.multiprobe_offsets})")
        cfg = cfg.replace(multiprobe_offsets=1)
    db, tsdb = _ssh_db(arch, cfg, db_dir, smoke=smoke)
    engine = tsdb.engine
    rng = np.random.default_rng(0)
    qids = rng.integers(0, db.shape[0], requests)

    # warm every padded bucket size outside the measured window (through
    # the engine's searcher directly so metrics only cover real requests)
    # — the dynamic batcher may form any bucket depending on arrival timing
    for size in cfg.buckets():
        engine.searcher.search_batch(db[jnp.asarray(np.resize(qids, size))])

    t0 = time.perf_counter()
    with tsdb:
        futs = [(int(i), tsdb.submit(db[int(i)])) for i in qids]
        for i, fut in futs:
            res = fut.result()
            print(f"req {i}: top1={res.ids[0]} pruned="
                  f"{res.pruned_total_frac:.1%}")
        wall = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
    print(f"engine: {engine.metrics.format()}")
    if replication > 1:
        print(f"fleet: hedged={snap['hedged_total']:.0f} "
              f"failovers={snap['failovers_total']:.0f} "
              f"degraded={snap['degraded_total']:.0f} "
              f"rebalanced={snap['rebalanced_shards_total']:.0f}")
    print(f"served {requests} requests in {wall:.2f}s "
          f"({requests / wall:.1f} qps end-to-end, "
          f"avg batch {snap['batch_size_mean']:.1f})")


def serve_ssh_sequential(arch, requests: int, backend: str = "auto",
                         db_dir=None, smoke: bool = True):
    """Pre-engine baseline: the sequential ``local`` searcher."""
    cfg = arch.search_config(length=SERVE_LENGTH, searcher="local",
                             backend=backend)
    db, tsdb = _ssh_db(arch, cfg, db_dir, smoke=smoke)
    rng = np.random.default_rng(0)
    lat = []
    for i in rng.integers(0, db.shape[0], requests):
        t0 = time.perf_counter()
        res = tsdb.search(db[int(i)])
        lat.append(time.perf_counter() - t0)
        print(f"req {i}: top1={res.ids[0]} pruned="
              f"{res.pruned_total_frac:.1%} {lat[-1]*1e3:.0f}ms")
    lat = sorted(lat)
    print(f"p50={lat[len(lat)//2]*1e3:.0f}ms "
          f"p99={lat[-1]*1e3:.0f}ms over {requests} requests "
          f"({requests / sum(lat):.1f} qps)")


def serve_lm(arch, requests: int, smoke: bool):
    from repro.models.transformer import decode_step, init_cache, prefill
    cfg = arch.smoke_config if smoke else arch.config
    params = steps_mod.init_fn(arch, "decode_32k", smoke=smoke)()
    b, prompt_len, gen_len = 2, 16, 8
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, prompt_len)),
                       jnp.int32)
    cache = init_cache(cfg, b, prompt_len + gen_len)
    decode = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))
    # prefill by stepping (simple serving loop; batched prefill also works)
    t0 = time.perf_counter()
    for i in range(prompt_len):
        logits, cache = decode(params, cache, toks[:, i:i + 1])
    out = []
    for _ in range(gen_len):
        nxt = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        out.append(nxt)
        logits, cache = decode(params, cache, nxt)
    gen = jnp.concatenate(out, axis=1)
    dt = time.perf_counter() - t0
    print(f"generated {gen.shape} tokens in {dt:.2f}s "
          f"({b * (prompt_len + gen_len) / dt:.1f} tok/s); "
          f"sample: {np.asarray(gen[0])}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=8,
                    help="dynamic batcher max batch (ssh only)")
    ap.add_argument("--wait-ms", type=float, default=2.0,
                    help="dynamic batcher max wait (ssh only)")
    ap.add_argument("--batch-mode", default="fixed",
                    choices=("fixed", "adaptive"),
                    help="batcher policy: fixed two-knob deadline or "
                         "queue-depth/EWMA adaptive wait (ssh only)")
    ap.add_argument("--sequential", action="store_true",
                    help="bypass the engine; one ssh_search per request")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "pallas", "jnp"),
                    help="kernel backend for the ssh query path "
                         "(collision count + DTW re-rank)")
    ap.add_argument("--db-dir", default=None,
                    help="serve a TimeSeriesDB saved here instead of "
                         "rebuilding the index (ssh only)")
    ap.add_argument("--replication", type=int, default=1,
                    help="replicas per shard; >= 2 serves through the "
                         "resilient fleet tier (ssh engine only)")
    ap.add_argument("--fleet-workers", type=int, default=None,
                    help="fleet size (default max(2, replication))")
    ap.add_argument("--hedge-ms", type=float, default=30.0,
                    help="hedging deadline floor in ms (fleet only)")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced smoke widths (default); --no-smoke "
                         "builds with the arch's published widths")
    args = ap.parse_args()
    enable_compile_cache()
    arch = get_arch(args.arch)
    if arch.family == "ssh":
        if args.sequential:
            serve_ssh_sequential(arch, args.requests, backend=args.backend,
                                 db_dir=args.db_dir, smoke=args.smoke)
        else:
            serve_ssh(arch, args.requests, args.batch_size, args.wait_ms,
                      backend=args.backend, db_dir=args.db_dir,
                      replication=args.replication,
                      fleet_workers=args.fleet_workers,
                      hedge_ms=args.hedge_ms, batch_mode=args.batch_mode,
                      smoke=args.smoke)
    elif arch.family == "lm":
        serve_lm(arch, args.requests, args.smoke)
    else:
        raise SystemExit(f"serving loop not defined for {arch.family}")


if __name__ == "__main__":
    main()
