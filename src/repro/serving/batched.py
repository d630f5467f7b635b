"""Batched end-to-end SSH search primitives (DESIGN.md §4).

One ``ssh_search_batch`` call serves a (B, m) block of queries through the
same three stages as the sequential ``repro.core.search.ssh_search``:

  1. **Batched signatures** — one vmap'd dispatch for the whole block
     (``SSHIndex.query_signatures_batch``), multiprobe offsets included.
  2. **Batched collision top-C** — a single (B, L) × (N, L) → (B, N) count
     (fused Pallas kernel on TPU, jnp reference elsewhere) + per-row
     ``top_k``, so the database streams from HBM once per *batch*.
  3. **Unified re-rank** (``repro.core.rerank.rerank_batch``) — seed DTW
     for a per-query best-so-far, the staged LB cascade (envelopes
     precomputed on the index when available), and backend-dispatched
     banded DTW over the flattened survivor pairs gathered through the
     deduped *union* candidate table.  Total DTW work is exactly the
     batch's survivor count — sequential-optimal, with no batch-max-width
     padding and one compiled program for every batch size.

Equality contract: per-query top-k (ids and distances) is identical to
sequential ``ssh_search`` with the same parameters — the probe uses the
same integer collision counts and the same ``lax.top_k`` tie-breaking,
and the re-rank is the same ``repro.core.rerank`` pipeline (same
best-so-far, same cascade decisions, same DTW values).
``tests/test_serving.py`` holds this contract over a synthetic-ECG
database; ``tests/test_rerank.py`` additionally holds it across the
"jnp" and "pallas" backends.

Shapes are bucketed (B by the engine, U to the next power of two) so a
steady request stream hits a handful of compiled programs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench.timing import DISABLED, STAGES, StageTimer, to_host
from repro.core import minhash
from repro.core import rerank as rr
from repro.core.index import SSHIndex
from repro.core.rerank import SearchStats
from repro.core.search import SearchResult
from repro.db.config import SearchConfig, config_from_legacy_kwargs
from repro.kernels import ops


@dataclasses.dataclass
class BatchSearchResult:
    """Per-query top-k plus the pruning statistics of the shared stages."""
    ids: np.ndarray                   # (B, k) database ids, best first
    dists: np.ndarray                 # (B, k) squared DTW costs
    n_queries: int
    n_database: int
    n_union: int                      # distinct candidates gathered per batch
    n_candidates: np.ndarray          # (B,) candidates reaching the DTW stage
    pruned_by_hash_frac: np.ndarray   # (B,)
    pruned_total_frac: np.ndarray     # (B,)
    wall_seconds: float
    stats: Optional[SearchStats] = None   # batch-aggregate rerank counters

    @property
    def dtw_evals(self) -> int:
        """Wavefront evaluations across the batch (re-rank survivors)."""
        return int(self.n_candidates.sum())

    def per_query(self, b: int) -> SearchResult:
        """Adapter: query ``b``'s slice as a sequential SearchResult.

        Filler rows (id −1, only when a query had fewer survivors than
        topk) are trimmed so lengths match the sequential path.  The
        rerank counters are tracked per *batch*, not per query, so
        ``stats`` stays None here (the sequential invariant
        ``stats.n_dtw == n_candidates`` would not hold for a slice);
        read the aggregate from ``BatchSearchResult.stats``.
        """
        k = int(np.sum(self.ids[b] >= 0))
        return SearchResult(
            ids=self.ids[b][:k], dists=self.dists[b][:k],
            n_candidates=int(self.n_candidates[b]),
            n_database=self.n_database,
            pruned_by_hash_frac=float(self.pruned_by_hash_frac[b]),
            pruned_total_frac=float(self.pruned_total_frac[b]),
            wall_seconds=self.wall_seconds)


def batch_probe(queries: jnp.ndarray, index: SSHIndex, top_c: int,
                rank_by_signature: bool = True,
                multiprobe_offsets: int = 1,
                use_pallas: Optional[bool] = None,
                interpret: bool = False,
                timer: StageTimer = DISABLED,
                probe_stats: Optional[dict] = None):
    """Stage 1+2 for a query block: (B, m) -> host arrays ids (B, C)
    int64, counts (B, C).

    Per-row decisions identical to the sequential ``hash_probe``: the same
    collision counts feed the same ``lax.top_k`` (ties → lowest id), and a
    row with no positive count falls back to the first C ids.  An enabled
    ``timer`` records the batched signature build as ``encode`` and the
    collision scan + top-C, its fetch and the fallback as ``probe``.

    Rows ride the index's signature LRU: when EVERY row is cached the
    batched encode dispatch is skipped entirely (``probe_stats`` gets
    ``{"sig_cache_hit": B}``); a partial hit re-encodes the whole block
    — one fused dispatch beats per-row gather/encode splicing — and
    populates the cache, reporting 0 (no work was actually skipped).
    Cached rows are the arrays the encoder produced, so candidate
    decisions are unchanged either way.
    """
    b = queries.shape[0]
    top_c = min(top_c, int(index.signatures.shape[0]))
    variant = (f"mp{multiprobe_offsets}" if multiprobe_offsets > 1
               else "sig")
    with timer.stage("encode") as sync:
        cache = index._sig_cache()
        rows = to_host(queries)
        keys = [cache.key(rows[i], index.enc.spec, index.build_backend,
                          variant) for i in range(b)]
        cached = [cache.get(k) for k in keys]
        hits = 0
        if all(r is not None for r in cached):
            sigs = jnp.asarray(np.stack(cached))          # (B, K)|(B, O, K)
            hits = b
        elif multiprobe_offsets > 1:
            sigs = index.query_signatures_batch_multiprobe(
                queries, multiprobe_offsets)              # (B, O, K)
        else:
            sigs = index.query_signatures_batch(queries)  # (B, K)
        if not hits:
            sig_rows = to_host(sigs)
            for i in range(b):
                cache.put(keys[i], sig_rows[i])
        if probe_stats is not None:
            probe_stats["sig_cache_hit"] = hits
        flat = (sigs.reshape(-1, sigs.shape[-1])
                if multiprobe_offsets > 1 else sigs)      # (B·O, K)
        if rank_by_signature:
            qk, db = flat, index.signatures
        else:
            qk = minhash.combine_bands(flat,
                                       index.num_tables).astype(jnp.int32)
            db = index.keys.astype(jnp.int32)
        qk = sync(qk)
    with timer.stage("probe") as sync:
        counts = ops.collision_count_batch(qk, db, use_pallas=use_pallas,
                                           interpret=interpret)   # (B·O, N)
        if multiprobe_offsets > 1:
            counts = counts.reshape(b, multiprobe_offsets, -1).max(axis=1)
        vals, ids = jax.lax.top_k(counts, top_c)
        ids, vals = sync((ids, vals))
        ids = to_host(ids).astype(np.int64)
        vals = to_host(vals)
        empty = ~(vals > 0).any(axis=1)
        if empty.any():        # degenerate rows: same fallback as sequential
            ids[empty] = np.arange(top_c, dtype=np.int64)[None, :]
    return ids, vals


def ssh_search_batch(queries: jnp.ndarray, index: SSHIndex,
                     config: Optional[SearchConfig] = None, *,
                     use_pallas: Optional[bool] = None,
                     **legacy_kwargs) -> BatchSearchResult:
    """Batched paper Alg. 2 over a (B, m) query block.

    Canonical form: ``ssh_search_batch(Q, index, config=SearchConfig(...))``
    — the same frozen config every entry point consumes; returns
    per-query top-k identical to ``ssh_search(q, index, config=...)`` for
    every row q (see module docstring for why).  The ``TimeSeriesDB``
    facade routes here for ``searcher="batched"``.

    Deprecation shim (one release): loose kwargs (``topk=..., top_c=...``)
    are folded into a ``SearchConfig`` under a ``DeprecationWarning``.
    ``use_pallas`` stays a probe-only kernel override for tests (defaults
    to the config backend's resolution when unset) — it is an
    implementation toggle, not a search knob, so it lives outside the
    config.
    """
    if config is not None and not isinstance(config, SearchConfig):
        # legacy positional call ssh_search_batch(Q, index, 10): the
        # third parameter used to be topk — fold into the kwarg shim
        legacy_kwargs["topk"] = config
        config = None
    if config is None:
        config = config_from_legacy_kwargs("ssh_search_batch",
                                           legacy_kwargs)
    elif legacy_kwargs:
        raise TypeError("ssh_search_batch() takes either config= or "
                        "legacy search kwargs, not both: "
                        f"{sorted(legacy_kwargs)}")
    t0 = time.perf_counter()
    timer = StageTimer(enabled=config.stage_timings, prefill=STAGES)
    queries = jnp.asarray(queries)
    b, m = queries.shape
    n = int(index.signatures.shape[0])
    c = min(config.top_c, n)
    if use_pallas is None:
        use_pallas = ops.resolve_backend(config.backend)

    # -- stages 1+2: fused probe ------------------------------------------
    probe_stats: dict = {}
    ids, vals = batch_probe(queries, index, c,
                            rank_by_signature=config.rank_by_signature,
                            multiprobe_offsets=config.multiprobe_offsets,
                            use_pallas=use_pallas, timer=timer,
                            probe_stats=probe_stats)      # (B, C) each
    valid = vals > 0
    valid[~valid.any(axis=1)] = True      # fallback rows: every id counts
    n_hash = valid.sum(axis=1)                            # (B,)

    # -- stage 3: unified re-rank (cascade + backend-dispatched DTW) ------
    out_ids, out_d, n_final, n_union, stats = rr.rerank_batch(
        queries, ids, valid, index, config.topk, config.band,
        use_lb_cascade=config.use_lb_cascade, backend=config.backend,
        seed_size=config.seed_size, early_abandon=config.early_abandon,
        timer=timer)
    if stats is not None:
        stats.index_bytes = index.nbytes()
        stats.sig_cache_hit = probe_stats.get("sig_cache_hit", 0)

    wall = time.perf_counter() - t0
    return BatchSearchResult(
        ids=out_ids, dists=out_d,
        n_queries=b, n_database=n, n_union=n_union,
        n_candidates=n_final,
        pruned_by_hash_frac=1.0 - n_hash / n,
        pruned_total_frac=1.0 - n_final / n,
        wall_seconds=wall, stats=stats)
