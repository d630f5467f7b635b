"""Unified DTW re-rank pipeline (DESIGN.md §3) — the hot path of Alg. 2.

Every query path that turns hash candidates into a top-k — sequential
``core.search.ssh_search``, batched ``serving.batched.ssh_search_batch``,
and the shard-local re-rank of ``distributed.dist_index`` — funnels
through this module, so the three stay decision-identical by
construction:

  1. **Seed DTW** over the first ``topk`` hash hits gives a per-query
     best-so-far (Lemire's two-pass idea: one cheap DTW pass buys a tight
     pruning threshold for the bound pass).
  2. **LB cascade** (``lower_bounds.cascade_staged``), cheapest bound
     first — LB_Kim O(1) → LB_Keogh O(m) → LB_Keogh2, the last fed by
     the candidate envelopes precomputed on ``SSHIndex`` when available
     (gather+compare instead of an O(m·r) envelope per query).  The
     cascade statically thins the top-C block to a survivor block; which
     bound fired first is counted into ``SearchStats``.
  3. **Banded DTW** over the survivors through one backend knob
     (``backend="pallas" | "jnp" | "auto"``, the same tri-state as the
     collision-count kernel): the Pallas anti-diagonal wavefront
     (``kernels.dtw_wavefront``) on TPU — lane-axis padding and the
     transposed/time-reversed layout live in the kernel wrapper — and the
     ``dtw_batch`` scan oracle on CPU.  Band-bounded DP replaces the UCR
     suite's data-dependent early abandoning (PrunedDTW line): the work
     bound is static, which is what lets the survivor block run as one
     dependence-free vector program.

Equality contract: for the same inputs the "jnp" and "pallas" backends
return identical top-k ids (the kernels are tested value-equal to the
oracle), and the batched entry point returns per-query results identical
to the sequential one — ``tests/test_rerank.py`` holds both.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench.timing import DISABLED, StageTimer, to_host
from repro.core import lower_bounds as lb
from repro.core.index import SSHIndex
from repro.kernels import ops

BIG = np.float32(1e30)

PAIR_CHUNK = 256        # survivor pairs per DTW dispatch (lane stability)
PAIR_CHUNK_SMALL = 32   # remainder granularity (bounds padding waste)


@dataclasses.dataclass
class SearchStats:
    """Re-rank pruning counters (paper Tables 1/4 instrumentation).

    The stage counters attribute each pruned candidate to the *first*
    bound that fired (cascade order: Kim → Keogh → Keogh2 → Improved),
    with the seeded candidates — which are exempt from pruning — never
    counted, so ``n_in == pruned_kim + pruned_keogh + pruned_keogh2 +
    pruned_improved + n_dtw``.  ``dtw_abandoned`` counts the survivors
    the threshold-aware DTW stage then abandoned mid-kernel (early-
    abandoning PrunedDTW) — a subset of ``n_dtw``, since those lanes
    still entered the DTW stage but stopped before the final diagonal.

    ``stage_seconds`` holds the per-stage wall clock of the whole query
    (``repro.bench.timing.STAGES``: encode → probe → lb → lb_improved →
    dtw, device-synchronized at each boundary; the ``lb`` stage includes
    the seed DTW that buys the pruning threshold).  ``None`` when
    telemetry was off (``SearchConfig(stage_timings=False)``); the
    distributed fan-out reports its unsplittable shard_map program under
    the single ``"fused"`` key instead.
    """
    n_in: int = 0            # candidates entering the re-rank stage
    pruned_kim: int = 0      # first pruned by LB_Kim
    pruned_keogh: int = 0    # survived Kim, pruned by LB_Keogh
    pruned_keogh2: int = 0   # survived both, pruned by LB_Keogh2
    pruned_improved: int = 0  # survived the trio, pruned by LB_Improved
    forced_kept: int = 0     # seeds kept despite a bound firing
    n_dtw: int = 0           # survivors that entered the DTW stage
    dtw_abandoned: int = 0   # of those, abandoned over the threshold
    backend: str = "jnp"     # resolved DTW backend ("pallas" | "jnp")
    stage_seconds: Optional[Dict[str, float]] = None
    # resident bytes of the index that served this query (artifacts +
    # encoder state — ``SSHIndex.nbytes``); makes the sketch-vs-exact
    # memory claim machine-readable next to the latency it bought
    index_bytes: Optional[int] = None
    # queries in this search whose encode was served from the signature
    # LRU (repro.encoders.sigcache) — 0/1 sequentially, up to B batched
    sig_cache_hit: int = 0
    # fleet resilience counters (repro.fleet; 0/False outside it):
    # shard calls re-issued to a replica on a lapsed hedging deadline,
    # shard calls re-issued after a worker fault, and whether any shard
    # of this search was answered by a non-primary replica — results
    # are bit-identical either way, the flags only mark that the fleet
    # was coping
    hedged: int = 0
    failovers: int = 0
    degraded: bool = False
    # sliding windows probed when the query ran against a subsequence
    # index (repro.subseq); 0 for whole-series search.  Subsequence
    # stats also carry the extra "encode_amortized" stage key: the
    # build-side rolling encode seconds divided over the indexed
    # windows — the per-window cost this query's probe amortises
    n_windows: int = 0

    @property
    def lb_pruned(self) -> int:
        return (self.pruned_kim + self.pruned_keogh + self.pruned_keogh2
                + self.pruned_improved)

    @property
    def lb_pruned_frac(self) -> float:
        return self.lb_pruned / self.n_in if self.n_in else 0.0

    @property
    def dtw_abandoned_frac(self) -> float:
        """Fraction of DTW-stage lanes abandoned over the threshold."""
        return self.dtw_abandoned / self.n_dtw if self.n_dtw else 0.0

    @property
    def stage_us(self) -> Optional[Dict[str, float]]:
        """``stage_seconds`` in microseconds (None when telemetry off)."""
        if self.stage_seconds is None:
            return None
        return {k: v * 1e6 for k, v in self.stage_seconds.items()}


# ---------------------------------------------------------------------------
# backend-dispatched DTW primitives
# ---------------------------------------------------------------------------

def dtw_candidates(query: jnp.ndarray, candidates: jnp.ndarray,
                   band: Optional[int], backend: str = "auto",
                   threshold=None) -> jnp.ndarray:
    """One query vs a candidate block, (m,) x (C, m) -> (C,).

    ``threshold`` (scalar) enables the early-abandon contract: lanes
    whose exact cost exceeds it return BIG instead (see ``kernels.ops``).
    """
    return ops.dtw_rerank(query, candidates, band,
                          use_pallas=ops.resolve_backend(backend),
                          threshold=threshold)


def dtw_pairs_chunked(q_rows: jnp.ndarray, c_rows: jnp.ndarray,
                      band: Optional[int], backend: str = "auto",
                      threshold=None) -> np.ndarray:
    """Row-aligned pair DTW in fixed-shape chunks: (P, m) x (P, m) -> (P,).

    Full PAIR_CHUNK blocks first, then the remainder at PAIR_CHUNK_SMALL
    granularity — two compiled programs serve every batch size and
    survivor count, the working set per dispatch stays cache-sized, and
    padding waste is bounded by PAIR_CHUNK_SMALL - 1 evaluations.

    ``threshold`` (scalar or (P,)) applies the per-lane early-abandon
    contract; padding lanes repeat row 0's threshold so they abandon with
    it instead of holding a chunk alive.
    """
    use_pallas = ops.resolve_backend(backend)
    p = int(q_rows.shape[0])
    pad = (-p) % PAIR_CHUNK_SMALL
    # padding is host-side numpy: P is data-dependent, and a device
    # concat on a fresh (P, m) shape compiles per distinct P (see the
    # union-table comment in rerank_batch) — only the fixed-shape
    # chunks below may touch the device
    q_rows = to_host(q_rows)
    c_rows = to_host(c_rows)
    thr = None
    if threshold is not None:
        thr = np.broadcast_to(
            np.asarray(threshold, np.float32).reshape(-1), (p,))
    if pad:
        q_rows = np.concatenate([q_rows, q_rows[:1].repeat(pad, 0)], 0)
        c_rows = np.concatenate([c_rows, c_rows[:1].repeat(pad, 0)], 0)
        if thr is not None:
            thr = np.concatenate([thr, thr[:1].repeat(pad, 0)], 0)
    out, i, total = [], 0, p + pad
    for chunk in (PAIR_CHUNK, PAIR_CHUNK_SMALL):
        while total - i >= chunk:
            out.append(to_host(ops.dtw_rerank_pairs(
                q_rows[i:i + chunk], c_rows[i:i + chunk], band,
                use_pallas=use_pallas,
                threshold=None if thr is None else thr[i:i + chunk])))
            i += chunk
    return np.concatenate(out)[:p]


def lb_improved_pairs_chunked(q_rows: jnp.ndarray, c_rows: jnp.ndarray,
                              band: int) -> np.ndarray:
    """Row-aligned LB_Improved in the same fixed-shape chunks as
    ``dtw_pairs_chunked``: (P, m) x (P, m) -> (P,).

    The survivor-pair count P is data-dependent, so jitting on the raw
    (P, m) shape compiles a fresh executable for nearly every live batch
    — under real traffic (distinct queries per batch) the re-rank spends
    ~10x its compute in XLA compilation, invisible to the device-synced
    stage timers.  Chunking caps the shape set at two programs.  The
    bound is lane-independent, so padding lanes (row 0 repeated) never
    change the first P values.
    """
    p = int(q_rows.shape[0])
    if not p:
        return np.zeros(0, np.float32)
    pad = (-p) % PAIR_CHUNK_SMALL
    q_rows = to_host(q_rows)           # host-side pad: see dtw_pairs_chunked
    c_rows = to_host(c_rows)
    if pad:
        q_rows = np.concatenate([q_rows, q_rows[:1].repeat(pad, 0)], 0)
        c_rows = np.concatenate([c_rows, c_rows[:1].repeat(pad, 0)], 0)
    out, i, total = [], 0, p + pad
    for chunk in (PAIR_CHUNK, PAIR_CHUNK_SMALL):
        while total - i >= chunk:
            out.append(to_host(lb.lb_improved_pairs(
                q_rows[i:i + chunk], c_rows[i:i + chunk], band)))
            i += chunk
    return np.concatenate(out)[:p]


# ---------------------------------------------------------------------------
# cascade thinning
# ---------------------------------------------------------------------------

def _staged_keep(query: jnp.ndarray, cands: jnp.ndarray, band: int,
                 best: jnp.ndarray,
                 cand_env: Optional[Tuple[jnp.ndarray, jnp.ndarray]]):
    """(keep1, keep2, keep3) numpy masks for one query's candidate block."""
    if cand_env is not None:
        k1, k2, k3 = lb.cascade_staged(query, cands, band, best,
                                       cand_env[0], cand_env[1])
    else:
        k1, k2, k3 = lb.cascade_staged(query, cands, band, best)
    return np.asarray(k1), np.asarray(k2), np.asarray(k3)


def _count_stages(k1: np.ndarray, k2: np.ndarray, k3: np.ndarray,
                  forced: np.ndarray) -> Tuple[np.ndarray, int, int, int,
                                               int]:
    """Survivor mask + first-bound-fired counters, seeds exempt.

    ``forced`` rows are kept regardless, and excluded from the stage
    counters, so counters partition the non-forced candidates exactly.
    """
    k1f, k2f, k3f = k1 | forced, k2 | forced, k3 | forced
    keep = k1f & k2f & k3f
    pruned_kim = int(np.sum(~k1f))
    pruned_keogh = int(np.sum(k1f & ~k2f))
    pruned_keogh2 = int(np.sum(k1f & k2f & ~k3f))
    forced_kept = int(np.sum(forced & ~(k1 & k2 & k3)))
    return keep, pruned_kim, pruned_keogh, pruned_keogh2, forced_kept


def _gathered_env(index: SSHIndex, ids, band: int):
    """Candidate envelope rows when the index has them cached at ``band``
    (build-time precompute); None otherwise (computed per block)."""
    if index.env_radius == band and index.env_upper is not None \
            and int(index.env_upper.shape[0]) == int(index.series.shape[0]):
        gid = jnp.asarray(ids)
        return index.env_upper[gid], index.env_lower[gid]
    return None


# ---------------------------------------------------------------------------
# sequential entry point (used by core.search.ssh_search)
# ---------------------------------------------------------------------------

def rerank(query: jnp.ndarray, cand_ids: jnp.ndarray, index: SSHIndex,
           topk: int, band: Optional[int], *, use_lb_cascade: bool = True,
           backend: str = "auto", seed_size: Optional[int] = None,
           early_abandon: bool = True, timer: StageTimer = DISABLED):
    """Candidate ids -> (global ids, dists, stats), best first.

    Stage 2+3 of Alg. 2 for one query: seed DTW → LB cascade →
    LB_Improved over the survivors → threshold-aware survivor DTW, every
    DTW through the ``backend`` knob.  ``seed_size`` widens the seeded
    set beyond ``topk`` (``None`` — the default — seeds exactly
    ``topk``): the threshold becomes the topk-th best of a larger
    sample, i.e. tighter, buying more pruning for more up-front DTW.
    ``early_abandon`` threads that same threshold into the final DTW as
    well (lanes provably over it stop early and report BIG).  Top-k
    results are unchanged by any of these knobs — the threshold is
    always a valid upper bound on the final k-th distance, so a pruned
    or abandoned candidate can never belong to the answer set.

    An enabled ``timer`` (shared with ``hash_probe`` so one dict carries
    all five stages) records seed DTW + cascade as ``lb``, the survivor
    LB_Improved pass as ``lb_improved``, and the survivor DTW + top-k as
    ``dtw``; the accumulated timings are published on
    ``stats.stage_seconds``.
    """
    backend_used = ops.backend_name(ops.resolve_backend(backend))
    cands = index.series[cand_ids]
    n_hash = int(cand_ids.shape[0])
    stats = SearchStats(n_in=n_hash, backend=backend_used)
    thr = None

    if use_lb_cascade and band is not None and n_hash > topk:
        with timer.stage("lb") as sync:
            # best-so-far: topk-th best DTW over the seeded best-hash
            # hits.  The seed is clamped to >= topk (validate() also
            # enforces it): a smaller seed would make the threshold an
            # upper bound on a better-than-kth distance, unsoundly
            # pruning true answers.
            s = min(max(seed_size or 0, topk), n_hash)
            seed = dtw_candidates(query, cands[:s], band, backend)
            best = jnp.sort(seed)[min(topk, s) - 1]
            env = _gathered_env(index, cand_ids, band)
            k1, k2, k3 = _staged_keep(query, cands, band, best, env)
            forced = np.zeros(n_hash, bool)
            forced[:s] = True                 # never drop the seeded set
            keep, p1, p2, p3, fk = _count_stages(k1, k2, k3, forced)
            stats.pruned_kim, stats.pruned_keogh, stats.pruned_keogh2 = \
                p1, p2, p3
            stats.forced_kept = fk
            keep_j = jnp.asarray(keep)
            cand_ids = sync(cand_ids[keep_j])
            cands = sync(cands[keep_j])
        with timer.stage("lb_improved") as sync:
            # Lemire's two-pass bound over cascade survivors only: it
            # needs the candidate-side envelope, which cannot be cached
            # at build time (it depends on the query via the clipped
            # series H), so the O(m·r) pass is paid after the cheap
            # bounds have thinned the block.
            lbi = np.asarray(sync(lb.lb_improved(query, cands, band)))
            forced_surv = forced[keep]
            pass123_surv = (k1 & k2 & k3)[keep]
            keep2 = (lbi < np.float32(best)) | forced_surv
            stats.pruned_improved = int(np.sum(~keep2))
            stats.forced_kept += int(np.sum(
                forced_surv & pass123_surv & (lbi >= np.float32(best))))
            keep2_j = jnp.asarray(keep2)
            cand_ids = sync(cand_ids[keep2_j])
            cands = sync(cands[keep2_j])
        if early_abandon:
            thr = best
    stats.n_dtw = int(cands.shape[0])

    with timer.stage("dtw") as sync:
        d = dtw_candidates(query, cands, band, backend, threshold=thr)
        k = min(topk, int(cands.shape[0]))
        vals, idx = jax.lax.top_k(-d, k)
        ids = np.asarray(cand_ids)[np.asarray(idx)]
        dists = np.asarray(-sync(vals))
    if thr is not None:
        stats.dtw_abandoned = int(np.sum(np.asarray(d) >= BIG * 0.5))
    if timer.enabled:
        stats.stage_seconds = dict(timer.timings)
    return ids, dists, stats


# ---------------------------------------------------------------------------
# batched entry point (used by serving.batched.ssh_search_batch)
# ---------------------------------------------------------------------------

def rerank_batch(queries: jnp.ndarray, ids: np.ndarray, valid: np.ndarray,
                 index: SSHIndex, topk: int, band: Optional[int], *,
                 use_lb_cascade: bool = True, backend: str = "auto",
                 seed_size: Optional[int] = None,
                 early_abandon: bool = True,
                 timer: StageTimer = DISABLED):
    """Batched stage 2+3 over per-query candidate blocks.

    queries (B, m); ids (B, C) int candidate ids; valid (B, C) bool.
    Returns (out_ids (B, k), out_d (B, k), n_final (B,), n_union, stats);
    filler rows (fewer survivors than topk) carry id -1 / dist BIG.

    Per-query decisions identical to ``rerank``: the same seed best-so-far
    feeds the same cascade and the same survivor LB_Improved pass,
    survivors are re-ranked with the same DTW values (pair DTW is
    lane-independent, hence bit-equal to the single-query block DTW), and
    the final ``lax.top_k`` applies the same tie-breaking.  The survivor
    (query, candidate) pairs are flattened through the deduped union
    candidate table and re-ranked in fixed-size chunks — total DTW work
    is exactly the batch's survivor count.  With ``early_abandon`` each
    pair lane carries its row's seed threshold into the DTW; rows whose
    cascade never applied (``n_hash <= topk`` — all pairs forced) get
    +inf so their fully-forced block is never masked.
    """
    backend_used = ops.backend_name(ops.resolve_backend(backend))
    b, c = ids.shape
    n_hash = valid.sum(axis=1)                            # (B,)
    stats = SearchStats(n_in=int(valid.sum()), backend=backend_used)
    k_out = min(topk, c)
    # seed clamped to >= topk for a sound threshold (see rerank())
    seed_k = min(max(seed_size or 0, topk), c)
    cascade_on = use_lb_cascade and band is not None
    thr_rows = None                                       # (B,) or None

    if cascade_on:
        with timer.stage("lb"):
            seed_series = index.series[jnp.asarray(ids[:, :seed_k])]
            seed_d = to_host(_seed_dtw_backend(queries, seed_series,
                                               band, backend))
            if seed_size is not None:
                # a widened seed may overrun a row's valid candidates
                # (only possible when seed_k > topk); mask those slots so
                # the threshold matches the sequential
                # min(seed_size, n_hash)
                col = np.arange(seed_k)[None, :]
                seed_d = np.where(col < n_hash[:, None], seed_d, np.inf)
                kth = np.sort(seed_d, axis=1)[:, min(topk, seed_k) - 1]
                best = jnp.asarray(kth.astype(np.float32))
            else:
                best = jnp.asarray(seed_d.max(axis=1))    # per-query kth-best
            cand_series = index.series[jnp.asarray(ids)]  # (B, C, m)
            env = _gathered_env(index, ids, band)
            if env is not None:
                k1, k2, k3 = _cascade_rows_env(queries, cand_series, band,
                                               best, env[0], env[1])
            else:
                k1, k2, k3 = _cascade_rows(queries, cand_series, band, best)
            k1, k2, k3 = to_host(k1), to_host(k2), to_host(k3)
            # sequential skips the cascade entirely when n_hash <= topk,
            # and never drops the seeded set; the first seed_k slots ARE
            # the first seed_k valid candidates whenever the cascade
            # applies (top_k sorts positive counts first)
            forced = np.zeros((b, c), bool)
            forced[:, :seed_k] = True
            forced[n_hash <= topk] = True
            # stage counters only over valid candidates that entered the
            # cascade (invalid slots never reach DTW; forced exempt)
            enter = valid & ~forced
            stats.pruned_kim = int(np.sum(enter & ~k1))
            stats.pruned_keogh = int(np.sum(enter & k1 & ~k2))
            stats.pruned_keogh2 = int(np.sum(enter & k1 & k2 & ~k3))
            stats.forced_kept = int(np.sum(valid & forced
                                           & ~(k1 & k2 & k3)))
            ok = valid & (forced | (k1 & k2 & k3))
            # per-row prune/abandon threshold; rows where the sequential
            # path skips the cascade (n_hash <= topk) are fully forced
            # and their seed kth may not upper-bound anything — +inf
            # exempts them from both LB_Improved and early abandoning
            thr_rows = np.where(np.asarray(n_hash) > topk,
                                to_host(best).astype(np.float32),
                                np.float32(np.inf)).astype(np.float32)
    else:
        ok = valid

    # flattened survivor pairs, through the deduped union table (built
    # here so the LB_Improved pass and the DTW reuse one gather).  All
    # pair bookkeeping stays host-side numpy: the pair count P is data-
    # dependent, and every eager device op on a fresh (P, m) shape — a
    # gather, a boolean mask, a pad concat — compiles its own tiny
    # executable.  One compile is cheap; under live traffic (new P every
    # batch) they dominate the re-rank wall clock.  Devices only see the
    # fixed-shape chunks inside the LB/DTW dispatchers.
    rows_idx, cols_idx = np.nonzero(ok)                   # (P,) row-major
    pair_ids = ids[rows_idx, cols_idx]
    union = np.unique(pair_ids)                           # (U,) sorted
    # a jax.Array keeps the host copy of its first np.asarray: the
    # series' is made once per index, the queries' by batch_probe's
    # encode, so neither crosses from the device here (no ssh.fetch)
    with timer.span("pairs", pairs=len(pair_ids), union=len(union)):
        series_np = np.asarray(index.series)   # zero-copy view on CPU jax
        union_series = series_np[union]                   # (U, m)
        pos = np.searchsorted(union, pair_ids)
        c_rows = union_series[pos]                        # (P, m)
        q_rows = np.asarray(queries)[rows_idx]            # (P, m)

    if cascade_on:
        with timer.stage("lb_improved") as sync:
            # survivor-only two-pass bound, same values as sequential
            # (per-row vmap of the identical elementwise program)
            lbi = sync(lb_improved_pairs_chunked(q_rows, c_rows, band))
            forced_pair = forced[rows_idx, cols_idx]
            pass123_pair = (k1 & k2 & k3)[rows_idx, cols_idx]
            thr_pair = thr_rows[rows_idx]
            keep_pair = (lbi < thr_pair) | forced_pair
            stats.pruned_improved = int(np.sum(~keep_pair))
            stats.forced_kept += int(np.sum(
                forced_pair & pass123_pair & ~(lbi < thr_pair)))
            ok[rows_idx[~keep_pair], cols_idx[~keep_pair]] = False
            rows_idx = rows_idx[keep_pair]
            cols_idx = cols_idx[keep_pair]
            q_rows = q_rows[keep_pair]
            c_rows = c_rows[keep_pair]
    n_final = ok.sum(axis=1)                              # (B,)

    with timer.stage("dtw", pairs=len(q_rows)) as sync:
        thr_pairs = (thr_rows[rows_idx]
                     if (cascade_on and early_abandon) else None)
        pair_d = dtw_pairs_chunked(q_rows, c_rows, band, backend,
                                   threshold=thr_pairs)   # (P,)
        stats.n_dtw = int(pair_d.shape[0])
        if thr_pairs is not None:
            stats.dtw_abandoned = int(np.sum(pair_d >= BIG * 0.5))

        # per-query top-k (lax.top_k for sequential-identical tie-breaks)
        cand_d = np.full((b, c), BIG, np.float32)         # candidate order
        cand_d[rows_idx, cols_idx] = pair_d
        neg, idx = jax.lax.top_k(-jnp.asarray(cand_d), k_out)
        idx = to_host(idx)
        out_ids = np.take_along_axis(ids, idx, axis=1)
        out_d = -to_host(sync(neg))
    # rows with fewer than k_out survivors: mark the filler tail (fixed
    # output shapes; callers trim these, matching sequential lengths)
    out_ids = np.where(out_d < BIG * 0.5, out_ids, -1)
    if timer.enabled:
        stats.stage_seconds = dict(timer.timings)
    return (out_ids.astype(np.int64), out_d.astype(np.float32),
            n_final.astype(np.int64), int(union.shape[0]), stats)


def _seed_dtw_backend(queries: jnp.ndarray, seed_series: jnp.ndarray,
                      band: Optional[int], backend: str) -> jnp.ndarray:
    """(B, m) x (B, s, m) -> (B, s) per-query seed DTW, via pair rows so
    the values are bit-identical to the flattened survivor-pair path."""
    b, s, m = seed_series.shape
    q_rows = jnp.repeat(queries, s, axis=0)               # (B·s, m)
    c_rows = seed_series.reshape(b * s, m)
    d = dtw_pairs_chunked(q_rows, c_rows, band, backend)
    return jnp.asarray(d.reshape(b, s))


def _cascade_rows(queries, cand_series, band, best):
    """vmap'd staged cascade: (B, m) x (B, C, m) -> three (B, C) masks."""
    fn = jax.vmap(lambda q, cs, b_: lb.cascade_staged(q, cs, band, b_))
    return fn(queries, cand_series, best)


def _cascade_rows_env(queries, cand_series, band, best, env_u, env_l):
    fn = jax.vmap(lambda q, cs, b_, u, l:
                  lb.cascade_staged(q, cs, band, b_, u, l))
    return fn(queries, cand_series, best, env_u, env_l)
