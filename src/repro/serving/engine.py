"""ServingEngine — dynamic batching on top of the batched SSH search.

Request lifecycle (DESIGN.md §4, §12):

  client -> submit() -> request deque -> batcher thread -> ssh_search_batch
                          (condition var)  |                      |
                                           +--- pending inserts --+-> futures

The batcher pulls the first waiting request, then keeps draining the
queue until the batch closes under the config's ``BatchPolicy``:
``mode="fixed"`` closes at ``max_batch`` requests or ``max_wait_ms``
after the batch opened (the classic two-knob trade-off);
``mode="adaptive"`` computes the wait from the instantaneous queue
depth, EWMAs of per-batch service seconds and inter-submit gaps, and
whether the batch opened from an idle engine
(``BatchPolicy.wait_budget_s`` — drain immediately when the queue
covers the batch, drain at ``min_wait`` when batches open back-to-back
or arrivals are too sparse to coalesce, stretch the wait only from an
idle engine seeing dense arrivals), so the engine rides the
latency/throughput knee without hand-tuning.  Either way the batch is padded up to a *bucketed*
size (powers of two ≤ ``max_batch``) so a steady stream of ragged batch
sizes hits a handful of compiled programs instead of recompiling per
size — and since batching only changes grouping and padding geometry,
answers are bit-identical across policies (enforced by test).

The request queue is a plain deque under one ``threading.Condition``:
``submit()`` wakes the batcher directly, so an idle engine burns no CPU
and wake-on-submit latency is not quantized by any poll interval.

Streaming inserts are routed through ``SSHIndex.insert`` on the batcher
thread, between batches — queries never race an index mutation, and every
query submitted after ``insert()`` returns is served by an index that
contains the new series.

Shard fan-out: ``DistributedSearcher`` answers the same ``search_batch``
contract through ``repro.distributed.dist_index`` (shard_map collision
scan + local DTW + one all_gather per query), so the engine can sit in
front of a multi-chip index unchanged.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.bench.timing import StageTimer
from repro.core.dtw import BIG
from repro.core.index import SSHIndex
from repro.core.rerank import SearchStats
from repro.core.search import SearchResult
from repro.db.config import SearchConfig
from repro.serving.batched import BatchSearchResult, ssh_search_batch
from repro.serving.metrics import ServingMetrics


class EngineConfig:
    """Removed alias of :class:`repro.db.SearchConfig` (one deprecation
    release, retired).  Constructing it raises with migration guidance —
    the search fields moved to ``SearchConfig`` unchanged, and the
    batcher knobs now live on ``SearchConfig.batch_policy`` as a
    :class:`repro.db.BatchPolicy`.
    """

    def __init__(self, *args, **kwargs):
        raise TypeError(
            "EngineConfig was removed; construct repro.db.SearchConfig "
            "instead (same search fields — batcher knobs now live on "
            "SearchConfig.batch_policy as a repro.db.BatchPolicy)")


class BatchedSearcher:
    """Default backend: the fused local batched path.

    Precomputes the database envelopes at ``config.band`` so every
    serving-path LB_Keogh2 is an O(m) gather+compare instead of an
    O(m·r) per-query envelope (DESIGN.md §3); ``SSHIndex.insert`` keeps
    the cache aligned under streaming inserts.
    """

    def __init__(self, index: SSHIndex, config: SearchConfig):
        self.index = index
        self.config = config
        if config.band is not None and config.use_lb_cascade \
                and index.series is not None:
            index.candidate_envelopes(config.band)

    def search_batch(self, queries: jnp.ndarray) -> BatchSearchResult:
        return ssh_search_batch(queries, self.index, config=self.config)

    def insert(self, series: jnp.ndarray) -> None:
        self.index.insert(series)

    def apply_artifacts(self, artifacts) -> None:
        """Fold pre-encoded streaming artifacts (``StreamArtifacts``)
        into the index — no re-hashing; ``insert_encoded`` keeps the
        envelope cache aligned."""
        self.index.insert_encoded(artifacts.series, artifacts.signatures,
                                  artifacts.keys)


class DistributedSearcher:
    """Shard fan-out backend over ``repro.distributed.dist_index``.

    Signatures and series are row-sharded over the mesh; each query in a
    batch runs the shard_map probe (local collision scan + local top-C/P +
    local DTW, one all_gather of k·2 scalars).  Batching here amortises
    the host dispatch loop; the per-query collective schedule is
    unchanged from the dry-run path.
    """

    def __init__(self, index: SSHIndex, config: SearchConfig, mesh):
        from repro.distributed import dist_index
        if config.band is None:
            raise ValueError("DistributedSearcher requires a band radius")
        # the shard_map probe ranks by raw signatures, single probe —
        # reject configs whose answers would silently differ from it
        # (use_lb_cascade is a pruning-perf knob: results are unchanged)
        if not config.rank_by_signature or config.multiprobe_offsets > 1:
            raise ValueError(
                "DistributedSearcher supports only rank_by_signature=True "
                "and multiprobe_offsets=1")
        self.index = index
        self.config = config
        self.mesh = mesh
        self._put_index_arrays()
        # encoder-generic shard fan-out: the encoder's materialised state
        # rides as a replicated operand; "ssh"/"srp"/"ssh-multires" (and
        # out-of-tree encoders) all serve through the same schedule
        self._state = index.enc.state()
        self._query_fn = dist_index.make_encoder_query_fn(
            index.enc, mesh, config=config)

    def _put_index_arrays(self) -> None:
        """(Re-)place the index rows under the mesh's shardings."""
        import jax
        from repro.distributed import dist_index
        n = int(self.index.signatures.shape[0])
        n_dev = self.mesh.devices.size
        if n % n_dev:
            raise ValueError(
                f"index rows ({n}) must divide the mesh ({n_dev} devices) "
                f"to row-shard; pad the stream to a multiple of {n_dev}")
        sig_sh, series_sh = dist_index.index_shardings(self.mesh)
        self._series = jax.device_put(self.index.series, series_sh)
        self._sigs = jax.device_put(self.index.signatures, sig_sh)

    def search_batch(self, queries: jnp.ndarray) -> BatchSearchResult:
        from repro.bench.timing import StageTimer
        from repro.kernels import ops
        t0 = time.perf_counter()
        timer = StageTimer(enabled=self.config.stage_timings)
        b = int(queries.shape[0])
        n = int(self.index.signatures.shape[0])
        ids, dists = [], []
        for i in range(b):                       # fan-out per query row
            # the shard_map program fuses encode/probe/DTW into one
            # dispatch, so its wall clock lands under the single
            # "fused" stage key (a per-stage split would need an
            # on-device profiler, not host timers)
            with timer.stage("fused") as sync:
                gid, d = sync(self._query_fn(self._series, self._sigs,
                                             self._state, queries[i]))
            ids.append(np.asarray(gid))
            dists.append(np.asarray(d))
        top_c = self.config.top_c
        stats = SearchStats(
            backend=ops.backend_name(ops.resolve_backend(
                self.config.backend)))
        if timer.enabled:
            stats.stage_seconds = dict(timer.timings)
        dists = np.stack(dists).astype(np.float32)
        # filler slots (fewer colliding rows than topk) carry id -1, as
        # the batched path's do, so per_query trims them
        ids = np.where(dists < BIG * 0.5, np.stack(ids), -1)
        return BatchSearchResult(
            ids=ids.astype(np.int64), dists=dists,
            n_queries=b, n_database=n, n_union=min(top_c, n),
            n_candidates=np.full(b, min(top_c, n), np.int64),
            pruned_by_hash_frac=np.full(b, 1.0 - min(top_c, n) / n),
            pruned_total_frac=np.full(b, 1.0 - min(top_c, n) / n),
            wall_seconds=time.perf_counter() - t0, stats=stats)

    def insert(self, series: jnp.ndarray) -> None:
        raise NotImplementedError(
            "streaming inserts into a sharded index require a reshard; "
            "stream through a StreamIngestor and fold with "
            "apply_artifacts() instead")

    def apply_artifacts(self, artifacts) -> None:
        """Fold pre-encoded streaming artifacts into the sharded index.

        The host-side index extends (signatures/keys/series), then the
        rows re-place under the same shardings — the shards receive
        *encoded* state, never raw series to re-hash.
        """
        self.index.insert_encoded(artifacts.series, artifacts.signatures,
                                  artifacts.keys)
        self._put_index_arrays()

    def resize(self, mesh) -> None:
        """Move the index to a new mesh (elastic shard count).

        Shard moves transfer the already-encoded rows and (via the
        encoder state operand) the sketch aggregate; nothing is
        re-encoded and no raw-series reshuffle happens beyond the
        device_put itself.
        """
        from repro.distributed import dist_index
        self.mesh = mesh
        self._state = self.index.enc.state()
        self._query_fn = dist_index.make_encoder_query_fn(
            self.index.enc, mesh, config=self.config)
        self._put_index_arrays()


def _lb_fracs(res: BatchSearchResult):
    """Batch-aggregate LB-cascade pruning fraction for metrics (empty when
    the backend ran no re-rank cascade, e.g. the distributed fan-out —
    whose stats exist only to carry stage timings, with n_in == 0)."""
    return ([res.stats.lb_pruned_frac]
            if res.stats is not None and res.stats.n_in else [])


def _abandon_fracs(res: BatchSearchResult):
    """Batch-aggregate early-abandoned DTW-lane fraction (empty when no
    lane entered the DTW stage, mirroring ``_lb_fracs``)."""
    return ([res.stats.dtw_abandoned_frac]
            if res.stats is not None and res.stats.n_dtw else [])


def _stage_seconds(res: BatchSearchResult):
    """Per-stage batch wall clock for metrics (None when telemetry off)."""
    return res.stats.stage_seconds if res.stats is not None else None


def _sig_hits(res: BatchSearchResult) -> int:
    """Queries in the batch whose encode came from the signature LRU."""
    return res.stats.sig_cache_hit if res.stats is not None else 0


def _fleet_counters(res: BatchSearchResult) -> dict:
    """Fleet resilience counters of the batch (zeros outside the fleet)."""
    s = res.stats
    if s is None:
        return {}
    return {"hedged": s.hedged, "failovers": s.failovers,
            "degraded": int(s.degraded)}


@dataclasses.dataclass
class _Request:
    query: jnp.ndarray
    future: Future
    t_enqueue: float


class ServingEngine:
    """Dynamic-batching query server over an SSHIndex.

    Usage::

        cfg = SearchConfig(band=8, batch_policy=BatchPolicy(max_batch=8))
        engine = ServingEngine(index, cfg)
        with engine:                       # starts the batcher thread
            fut = engine.submit(q)         # async
            res = engine.search(q)         # sync convenience
        engine.metrics.snapshot()

    (Or behind the facade: ``TimeSeriesDB`` with
    ``SearchConfig(searcher="engine")`` owns one of these.)

    ``search_batch`` bypasses the queue entirely (one caller already holds
    a full batch) but still records metrics — benchmarks use it to measure
    the compute path without batcher timing noise.
    """

    _STOP = object()

    def __init__(self, index: SSHIndex,
                 config: SearchConfig = SearchConfig(),
                 searcher=None, metrics: Optional[ServingMetrics] = None):
        self.index = index
        self.config = config
        if searcher is None:
            if config.replication > 1:
                # resilience requested: serve through the fleet tier
                # (replicated shards, hedged fan-out, drain/resize)
                from repro.fleet import FleetSearcher
                searcher = FleetSearcher(index, config)
            else:
                searcher = BatchedSearcher(index, config)
        self.searcher = searcher
        self.metrics = metrics or ServingMetrics()
        # request queue: a deque under one condition variable — submit()
        # wakes the batcher directly and _collect() reads the exact depth
        # (no polling, no qsize() approximation)
        self._cond = threading.Condition()
        self._pending: deque = deque()
        self._inserts: "queue.Queue" = queue.Queue()
        # EWMA of per-batch service seconds (stage-seconds sum when the
        # config collects them, batch wall clock otherwise) — the
        # adaptive policy's estimate of what one more batch costs
        self._service_ewma_s: Optional[float] = None
        self._arrival_gap_ewma_s: Optional[float] = None
        self._last_enqueue_t: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        # serializes index mutation vs. serving across the batcher thread
        # and direct search_batch() callers
        self._serve_lock = threading.Lock()
        # serializes submit()/insert() enqueues against stop()'s final
        # drain, so nothing enqueued concurrently with shutdown is lost.
        # States: "new" (pre-start: submits enqueue and are batched once
        # the worker starts), "running", "stopped" (submits serve on the
        # caller's thread).
        self._lifecycle_lock = threading.Lock()
        self._state = "new"

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "ServingEngine":
        if self._thread is not None:
            return self
        with self._lifecycle_lock:
            self._state = "running"
        self.metrics.on_start()
        self._thread = threading.Thread(target=self._worker,
                                        name="ssh-serving-batcher",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is None:
            return
        with self._cond:
            self._pending.append(self._STOP)
            self._cond.notify_all()
        self._thread.join()
        with self._lifecycle_lock:
            self._state = "stopped"
            self._thread = None
            with self._cond:
                stragglers = [r for r in self._pending
                              if r is not self._STOP]
                self._pending.clear()
        # requests/inserts that raced shutdown: resolve every future
        max_batch = self.config.batch_policy.max_batch
        for lo in range(0, len(stragglers), max_batch):
            chunk = stragglers[lo:lo + max_batch]
            try:
                results = self.search_batch(
                    jnp.stack([r.query for r in chunk], axis=0))
                for r, res in zip(chunk, results):
                    r.future.set_result(res)
            except Exception as exc:
                for r in chunk:
                    r.future.set_exception(exc)
        if not stragglers:
            with self._serve_lock:
                self._drain_inserts()

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client API -------------------------------------------------------
    def submit(self, query: jnp.ndarray) -> Future:
        """Enqueue one query; resolves to a per-query SearchResult.

        After stop() the query is served synchronously on the caller's
        thread (the future returns already resolved) — submit() never
        leaves a future dangling, even racing stop().
        """
        fut: Future = Future()
        query = jnp.asarray(query)
        with self._lifecycle_lock:
            enqueue = self._state != "stopped"
            if enqueue:
                with self._cond:
                    now = time.perf_counter()
                    if self._last_enqueue_t is not None:
                        gap = now - self._last_enqueue_t
                        alpha = self.config.batch_policy.ewma_alpha
                        prev = self._arrival_gap_ewma_s
                        self._arrival_gap_ewma_s = gap if prev is None \
                            else alpha * gap + (1.0 - alpha) * prev
                    self._last_enqueue_t = now
                    self._pending.append(_Request(query, fut, now))
                    depth = len(self._pending)
                    self._cond.notify_all()
        if enqueue:
            self.metrics.on_enqueue(depth)
        else:
            try:
                fut.set_result(self.search_batch(query[None, :])[0])
            except Exception as exc:
                fut.set_exception(exc)
        return fut

    def search(self, query: jnp.ndarray,
               timeout: Optional[float] = None) -> SearchResult:
        """Synchronous single query (through the batcher when running)."""
        if self._state == "running":
            return self.submit(query).result(timeout=timeout)
        return self.search_batch(jnp.asarray(query)[None, :])[0]

    def search_batch(self, queries: jnp.ndarray) -> List[SearchResult]:
        """Serve a caller-assembled batch directly (no queue)."""
        queries = jnp.asarray(queries)
        b = int(queries.shape[0])
        with StageTimer.span("batch", size=b, bucket=b, head_wait_us=0):
            t0 = time.perf_counter()
            with self._serve_lock:
                self._drain_inserts()
                res = self.searcher.search_batch(queries)
            wall = time.perf_counter() - t0
            with StageTimer.span("engine.deliver"):
                self.metrics.set_index_bytes(self.index.nbytes())
                self.metrics.on_batch(
                    b, [wall] * b, [0.0] * b,
                    list(res.pruned_by_hash_frac[:b]),
                    list(res.pruned_total_frac[:b]),
                    len(self._pending),
                    lb_pruned_frac=_lb_fracs(res),
                    dtw_abandoned_frac=_abandon_fracs(res),
                    stage_seconds=_stage_seconds(res),
                    sig_cache_hits=_sig_hits(res),
                    **_fleet_counters(res))
                return [res.per_query(i) for i in range(b)]

    def flush_inserts(self) -> None:
        """Apply queued streaming inserts to the index *now*.

        Normally inserts drain on the batcher thread between batches;
        persistence (``TimeSeriesDB.save``) calls this so a snapshot
        taken right after ``insert()`` returned contains the series.
        """
        with self._serve_lock:
            self._drain_inserts()

    def apply_artifacts(self, artifacts) -> None:
        """Fold pre-encoded streaming artifacts under the serve lock —
        like ``insert`` it never races an in-flight batch, and queued
        plain inserts drain first so index row order stays the arrival
        order."""
        with self._serve_lock:
            self._drain_inserts()
            self.searcher.apply_artifacts(artifacts)

    def drain(self, worker: str) -> int:
        """Gracefully retire a fleet worker while serving.

        Delegates to the fleet searcher's drain protocol: new shard
        calls route away from ``worker`` immediately, its in-flight
        calls finish and count, then its replica slots re-home from the
        published artifacts.  Queries queued in the engine keep flowing
        throughout — the batcher thread never stops, so zero queued
        queries are lost (chaos-tested in ``tests/test_fleet.py``).
        Returns the number of shards moved; raises ``AttributeError``
        when the active searcher has no drain support (not a fleet).
        """
        drain = getattr(self.searcher, "drain", None)
        if drain is None:
            raise AttributeError(
                f"searcher {type(self.searcher).__name__} does not "
                "support drain(); serve with config.replication > 1")
        moved = drain(worker)
        self.metrics.on_rebalance(moved)
        return moved

    def resize(self, workers) -> int:
        """Live fleet rebalance (int worker count or name list); returns
        shards moved.  Fleet-backed engines only."""
        resize = getattr(self.searcher, "resize", None)
        if resize is None:
            raise AttributeError(
                f"searcher {type(self.searcher).__name__} does not "
                "support resize(); serve with config.replication > 1")
        moved = resize(workers)
        self.metrics.on_rebalance(moved)
        return moved

    def insert(self, series: jnp.ndarray) -> None:
        """Streaming insert; visible to all queries submitted afterwards."""
        series = jnp.asarray(series)
        if series.ndim == 1:
            series = series[None, :]
        with self._lifecycle_lock:
            running = self._state == "running"
            if running:
                self._inserts.put(series)
        if not running:
            with self._serve_lock:
                self.searcher.insert(series)
        self.metrics.on_insert(int(series.shape[0]))

    @property
    def service_ewma_s(self) -> Optional[float]:
        """The adaptive policy's live service-time estimate (seconds per
        batch; None until the first batch completes)."""
        return self._service_ewma_s

    @property
    def arrival_gap_ewma_s(self) -> Optional[float]:
        """The adaptive policy's live inter-arrival estimate (seconds
        between submits; None until the second submit)."""
        return self._arrival_gap_ewma_s

    @property
    def queue_depth(self) -> int:
        """Requests waiting in the batcher queue right now."""
        with self._cond:
            return sum(1 for r in self._pending if r is not self._STOP)

    # -- batcher internals ------------------------------------------------
    def _drain_inserts(self) -> None:
        while True:
            try:
                series = self._inserts.get_nowait()
            except queue.Empty:
                return
            self.searcher.insert(series)

    def _bucket(self, b: int) -> int:
        """The compiled batch size a batch of ``b`` requests pads to."""
        return next(s for s in self.config.buckets() if s >= b)

    def _pad_batch(self, queries: List[jnp.ndarray]) -> jnp.ndarray:
        """Pad to the next bucket size by repeating the first query."""
        b = len(queries)
        block = list(queries) + [queries[0]] * (self._bucket(b) - b)
        return jnp.stack(block, axis=0)

    def _collect(self, first: _Request,
                 opened_idle: bool = True) -> List[_Request]:
        """Grow a batch around ``first`` under the config's BatchPolicy.

        The wait budget is recomputed from the live batch size and queue
        depth every time the state changes (fixed mode: constant budget),
        so the adaptive policy reacts within one condition-variable
        wake-up.  The budget counts from the moment the batch opened —
        arriving requests extend the batch, never the deadline.
        ``opened_idle`` records whether the worker had to sleep for
        ``first`` (idle engine: the adaptive policy may stretch the
        wait) or found it already queued (busy: drain at ``min_wait``).
        A ``_STOP`` sentinel is left in the deque for ``_worker``'s
        outer loop to consume.
        """
        pol = self.config.batch_policy
        batch = [first]
        t_open = time.perf_counter()
        with self._cond:
            while len(batch) < pol.max_batch:
                while self._pending and len(batch) < pol.max_batch:
                    if self._pending[0] is self._STOP:
                        return batch        # leave the sentinel in place
                    batch.append(self._pending.popleft())
                if len(batch) >= pol.max_batch:
                    break
                budget = pol.wait_budget_s(
                    len(batch), len(self._pending), self._service_ewma_s,
                    engine_idle=opened_idle,
                    arrival_gap_s=self._arrival_gap_ewma_s)
                remaining = t_open + budget - time.perf_counter()
                if remaining <= 0:
                    break
                if not self._cond.wait(timeout=remaining):
                    break                   # budget elapsed, nothing new
        return batch

    def _observe_service(self, res: BatchSearchResult,
                         wall_s: float) -> None:
        """Fold one batch's service time into the adaptive EWMA."""
        stage = _stage_seconds(res)
        sample = sum(stage.values()) if stage else wall_s
        alpha = self.config.batch_policy.ewma_alpha
        prev = self._service_ewma_s
        self._service_ewma_s = sample if prev is None \
            else alpha * sample + (1.0 - alpha) * prev

    def _worker(self) -> None:
        while True:
            with self._cond:
                opened_idle = not self._pending
                if not self._pending:
                    with StageTimer.span("engine.wait"):
                        while not self._pending:
                            self._cond.wait()
                item = self._pending.popleft()
            if item is self._STOP:
                return
            with StageTimer.span("engine.collect"):
                batch = self._collect(item, opened_idle)
            t0 = time.perf_counter()
            with StageTimer.span(
                    "batch", size=len(batch), bucket=self._bucket(len(batch)),
                    head_wait_us=round((t0 - batch[0].t_enqueue) * 1e6)):
                self._serve(batch, t0)

    def _serve(self, batch: List[_Request], t0: float) -> None:
        """Search one collected batch and resolve its futures."""
        try:                     # a failing insert also fails the batch
            with self._serve_lock:           # loudly (and keeps the worker
                self._drain_inserts()        # alive for later requests)
                block = self._pad_batch([r.query for r in batch])
                res = self.searcher.search_batch(block)
        except Exception as exc:
            for r in batch:
                r.future.set_exception(exc)
            return
        done = time.perf_counter()
        with StageTimer.span("engine.deliver"):
            self._observe_service(res, done - t0)
            for i, r in enumerate(batch):
                r.future.set_result(res.per_query(i))
            self.metrics.set_index_bytes(self.index.nbytes())
            self.metrics.on_batch(
                len(batch),
                [done - r.t_enqueue for r in batch],
                [t0 - r.t_enqueue for r in batch],
                list(res.pruned_by_hash_frac[:len(batch)]),
                list(res.pruned_total_frac[:len(batch)]),
                len(self._pending),
                lb_pruned_frac=_lb_fracs(res),
                dtw_abandoned_frac=_abandon_fracs(res),
                stage_seconds=_stage_seconds(res),
                sig_cache_hits=_sig_hits(res),
                batch_wait_s=t0 - batch[0].t_enqueue,
                batch_occupancy=(len(batch)
                                 / self.config.batch_policy.max_batch),
                **_fleet_counters(res))
