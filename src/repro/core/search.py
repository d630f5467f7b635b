"""End-to-end search pipelines: SSH (paper Alg. 2), UCR-suite baseline, SRP.

All three return ``SearchResult`` with pruning statistics so the paper's
Tables 1–4 can be produced from one code path.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench.timing import DISABLED, STAGES, StageTimer
from repro.core.dtw import dtw_batch
from repro.core import lower_bounds as lb
from repro.core import rerank as rr
from repro.core import srp as srp_mod
from repro.core.index import SSHIndex
from repro.core.rerank import SearchStats
from repro.db.config import SearchConfig, config_from_legacy_kwargs
from repro.kernels import ops
from repro.kernels import ref as kref


@dataclasses.dataclass
class SearchResult:
    ids: np.ndarray              # (k,) database ids, best first
    dists: np.ndarray            # (k,) squared DTW costs
    n_candidates: int            # candidates that reached the DTW stage
    n_database: int
    pruned_by_hash_frac: float   # paper Table 4 row "Pruned by Hashing alone"
    pruned_total_frac: float     # paper Table 4 row "SSH Algorithm (Full)"
    wall_seconds: float
    stats: Optional[SearchStats] = None   # re-rank cascade counters

    @property
    def dtw_evals(self) -> int:
        return self.n_candidates


def hash_probe(query: jnp.ndarray, index: SSHIndex, top_c: int,
               rank_by_signature: bool = True,
               multiprobe_offsets: int = 1,
               use_host_buckets: bool = False,
               topk: int = 10,
               backend: str = "auto",
               timer: StageTimer = DISABLED,
               probe_stats: Optional[dict] = None) -> jnp.ndarray:
    """Stage 1 of Alg. 2: candidate ids ranked by hash collisions.

    Returns at most ``top_c`` candidate ids with a positive collision
    count, most-promising first; falls back to the first ``top_c`` ids when
    nothing collides.  The batched counterpart lives in
    ``repro.serving.batched`` (identical per-query decisions).  The
    ``backend`` knob routes the collision count through the Pallas kernel
    or the jnp reference — integer counts, so candidate sets are identical
    either way.  An enabled ``timer`` records the query signature build
    as the ``encode`` stage and the collision scan + top-C as ``probe``.

    Encodes go through the index's signature LRU (keyed by query
    content + spec + backend — bit-identical on hit, so candidate sets
    are unchanged); a caller-supplied ``probe_stats`` dict receives
    ``{"sig_cache_hit": 0|1}`` for telemetry.
    """
    n = int(index.keys.shape[0])
    use_pallas = ops.resolve_backend(backend)
    hit = False
    if use_host_buckets and index.host_buckets is not None:
        with timer.stage("encode") as sync:
            qkeys, hit = index.query_keys_cached(query)
            qkeys = sync(qkeys)
        with timer.stage("probe") as sync:
            cand_ids = index.host_buckets.probe(np.asarray(qkeys))
            cand_ids = jnp.asarray(cand_ids[: max(top_c, topk)], jnp.int32)
    elif multiprobe_offsets > 1:
        # one probe row per δ-offset, combined by per-candidate max —
        # same qk/db selection as the batched batch_probe
        from repro.core import minhash
        with timer.stage("encode") as sync:
            qsigs, hit = index.query_signatures_multiprobe_cached(
                query, multiprobe_offsets)
            if rank_by_signature:
                qk, db = qsigs, index.signatures
            else:
                qk = minhash.combine_bands(qsigs, index.num_tables)
                db = index.keys
            qk = sync(qk)
        with timer.stage("probe") as sync:
            counts_max = jnp.max(jnp.stack(
                [ops.collision_count(row, db, use_pallas=use_pallas)
                 for row in qk]), axis=0)
            vals, ids = jax.lax.top_k(counts_max, min(top_c, n))
            cand_ids = sync(ids[vals > 0])
    else:
        with timer.stage("encode") as sync:
            if rank_by_signature:
                qk, hit = index.query_signature_cached(query)
                db = index.signatures
            else:
                qk, hit = index.query_keys_cached(query)
                db = index.keys
            qk = sync(qk)
        with timer.stage("probe") as sync:
            counts = ops.collision_count(qk, db, use_pallas=use_pallas)
            vals, ids = jax.lax.top_k(counts, min(top_c, n))
            cand_ids = sync(ids[vals > 0])
    if probe_stats is not None:
        probe_stats["sig_cache_hit"] = int(hit)
    if cand_ids.shape[0] == 0:           # degenerate: fall back to top_c ids
        cand_ids = jnp.arange(min(top_c, n), dtype=jnp.int32)
    return cand_ids


def ssh_search(query: jnp.ndarray, index: SSHIndex,
               config: Optional[SearchConfig] = None,
               **legacy_kwargs) -> SearchResult:
    """Paper Algorithm 2: hash-probe candidates, then DTW re-rank.

    Canonical form: ``ssh_search(q, index, config=SearchConfig(...))`` —
    every knob (topk, top_c, band, cascade, multiprobe, host buckets,
    seed size, kernel backend) lives on the one frozen config consumed
    by all entry points; see ``repro.db.SearchConfig`` for semantics.
    The ``TimeSeriesDB`` facade routes here for ``searcher="local"``.

    Deprecation shim (one release): the historical loose-kwarg form
    ``ssh_search(q, index, topk=..., top_c=..., band=..., ...)`` still
    works — the kwargs are folded into a ``SearchConfig`` (identical
    results) under a ``DeprecationWarning``.
    """
    if config is not None and not isinstance(config, SearchConfig):
        # legacy positional call ssh_search(q, index, 10): the third
        # parameter used to be topk — fold it into the kwarg shim
        legacy_kwargs["topk"] = config
        config = None
    if config is None:
        config = config_from_legacy_kwargs("ssh_search", legacy_kwargs)
    elif legacy_kwargs:
        raise TypeError("ssh_search() takes either config= or legacy "
                        "search kwargs, not both: "
                        f"{sorted(legacy_kwargs)}")
    t0 = time.perf_counter()
    timer = StageTimer(enabled=config.stage_timings, prefill=STAGES)
    n = int(index.keys.shape[0])
    probe_stats: dict = {}
    cand_ids = hash_probe(query, index, config.top_c,
                          rank_by_signature=config.rank_by_signature,
                          multiprobe_offsets=config.multiprobe_offsets,
                          use_host_buckets=config.use_host_buckets,
                          topk=config.topk, backend=config.backend,
                          timer=timer, probe_stats=probe_stats)
    n_hash = int(cand_ids.shape[0])

    ids, dists, stats = rr.rerank(query, cand_ids, index, config.topk,
                                  config.band,
                                  use_lb_cascade=config.use_lb_cascade,
                                  backend=config.backend,
                                  seed_size=config.seed_size,
                                  early_abandon=config.early_abandon,
                                  timer=timer)
    n_final = stats.n_dtw
    stats.index_bytes = index.nbytes()
    stats.sig_cache_hit = probe_stats.get("sig_cache_hit", 0)
    wall = time.perf_counter() - t0
    return SearchResult(
        ids=ids, dists=dists,
        n_candidates=n_final, n_database=n,
        pruned_by_hash_frac=1.0 - n_hash / n,
        pruned_total_frac=1.0 - n_final / n,
        wall_seconds=wall, stats=stats)


def ucr_search(query: jnp.ndarray, series: jnp.ndarray, topk: int = 10,
               band: Optional[int] = None, seed_size: int = 64,
               backend: str = "auto") -> SearchResult:
    """Vectorised UCR-suite: exact top-k via LB cascade + DTW on survivors.

    Decision-equivalent to the sequential suite: the LB cascade prunes
    against a best-so-far obtained from a seed subset, survivors get exact
    DTW (through the shared backend-dispatched re-rank primitive).
    (Exactness: a candidate is only dropped if some lower bound exceeds a
    *valid* upper bound on the k-th best distance.)
    """
    t0 = time.perf_counter()
    n = series.shape[0]
    seed = rr.dtw_candidates(query, series[:seed_size], band, backend)
    kth = jnp.sort(seed)[min(topk, seed_size) - 1]
    if band is None:
        # envelope bounds at a finite radius do NOT lower-bound the
        # unconstrained DTW (a path may align outside the window); only
        # LB_Kim (first/last point, forced by any warping path) is sound
        keep = lb.lb_kim(query, series) < kth
    else:
        keep = lb.cascade(query, series, band, kth)
    keep = keep.at[:seed_size].set(True)
    survivors = jnp.nonzero(keep, size=n, fill_value=n)[0]
    n_surv = int(jnp.sum(keep))
    cands = series[survivors[:n_surv]]
    d = rr.dtw_candidates(query, cands, band, backend)
    k = min(topk, int(cands.shape[0]))
    vals, idx = jax.lax.top_k(-d, k)
    ids = np.asarray(survivors[:n_surv])[np.asarray(idx)]
    wall = time.perf_counter() - t0
    return SearchResult(
        ids=ids, dists=np.asarray(-vals), n_candidates=n_surv,
        n_database=n, pruned_by_hash_frac=0.0,
        pruned_total_frac=1.0 - n_surv / n, wall_seconds=wall)


def brute_force_topk(query: jnp.ndarray, series: jnp.ndarray, topk: int,
                     band: Optional[int] = None):
    """Gold standard (paper §5.3): exact DTW over the whole database.

    Always the plain jnp reference (``kernels.ref``), on every platform,
    so the gold never runs the Pallas kernels it judges; it shares the
    banded window-DP's summation order with the re-rank's jnp backend,
    keeping exactness tests ulp-comparable.
    """
    d = kref.dtw_wavefront_ref(query, series, band=band)
    vals, idx = jax.lax.top_k(-d, topk)
    return np.asarray(idx), np.asarray(-vals)


def srp_search(query: jnp.ndarray, series: jnp.ndarray, planes: jnp.ndarray,
               db_bits: jnp.ndarray, topk: int = 10) -> SearchResult:
    """SRP baseline: rank by sign-bit Hamming similarity (no alignment)."""
    t0 = time.perf_counter()
    qb = srp_mod.srp_bits(query, planes)
    ids, _ = srp_mod.srp_topk(qb, db_bits, topk)
    d = dtw_batch(query, series[ids])
    wall = time.perf_counter() - t0
    return SearchResult(
        ids=np.asarray(ids), dists=np.asarray(d),
        n_candidates=topk, n_database=series.shape[0],
        pruned_by_hash_frac=1.0 - topk / series.shape[0],
        pruned_total_frac=1.0 - topk / series.shape[0],
        wall_seconds=wall)


def precision_at_k(pred_ids: np.ndarray, gold_ids: np.ndarray, k: int
                   ) -> float:
    """Paper §5.3: |top-k ∩ gold top-k| / k."""
    return len(set(pred_ids[:k].tolist()) & set(gold_ids[:k].tolist())) / k


def ndcg_at_k(pred_ids: np.ndarray, gold_ids: np.ndarray, k: int) -> float:
    """Paper §5.3 NDCG with graded relevance R_i = k - rank_gold(i)."""
    rel = {int(g): k - r for r, g in enumerate(gold_ids[:k].tolist())}
    dcg = sum(rel.get(int(p), 0) / np.log2(i + 2)
              for i, p in enumerate(pred_ids[:k].tolist()))
    idcg = sum((k - i) / np.log2(i + 2) for i in range(k))
    return float(dcg / idcg) if idcg > 0 else 0.0
